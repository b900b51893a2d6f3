"""What a ``train()`` call spends outside the builder, per call: the span
``train`` (``ModelBuilder.train``: trace, Job, worker thread, validation,
the model's registration) less the builders' own spans ``gbm.build`` and
``glm.build``, from the program's ``span_seconds`` histogram, as
differences over the traced call."""

TRAIN = "span_seconds_sum{name=train}"
CALLS = "span_seconds_count{name=train}"
BUILDS = ("span_seconds_sum{name=gbm.build}", "span_seconds_sum{name=glm.build}")
COUNTERS = (TRAIN, CALLS, *BUILDS)


def read(ctx):
    c = ctx["call"]["counters"]
    if not c.get(CALLS):  # no such span in this program, or its metrics are off
        return None
    return 1e3 * (c[TRAIN] - sum(c[b] for b in BUILDS)) / c[CALLS]
