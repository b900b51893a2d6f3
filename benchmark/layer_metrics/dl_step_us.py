"""Microseconds a DeepLearning minibatch step takes in the traced call: the
seconds of the epoch programs' dispatches (``dispatch:dl_chunk``, each
ending in the pull of its losses, so the device's time for the chunk lies
inside) over the minibatch steps they ran (``dl_minibatch_steps_total``,
counted at dispatch), both as differences over the call. The chunk's row
permutation is inside too (scope ``ph_dl_perm``). Nothing where the program
counts no steps."""

SECONDS = "dispatch_device_seconds_sum{site=dl_chunk}"
STEPS = "dl_minibatch_steps_total"
COUNTERS = (SECONDS, STEPS)


def read(ctx):
    c = ctx["call"]["counters"]
    if not c.get(STEPS):
        return None
    return 1e6 * c[SECONDS] / c[STEPS]
