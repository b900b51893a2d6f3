"""Seconds of the compile pipeline (trace, lower, compile or cache load) that
the traced ``train()`` call spent, as a difference over the call: 0 where the
window runs compiled programs only; anything else names a program that
recompiles in the steady state (the ring's ``compile`` events say which span)."""

from benchmark.layer_metrics import _compile_pipeline as _cp

COUNTERS = tuple(_cp.flat(_cp.SECONDS, s) for s in _cp.STAGES)


def read(ctx):
    if not _cp.heard():
        return None
    c = ctx["call"]["counters"]
    return sum(c[n] for n in COUNTERS)
