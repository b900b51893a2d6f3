"""Seconds of the span ``glm.fit`` (the solver: the IRLS chunk dispatches,
the lambda path, the coefficient output) for each GLM fit of the traced call."""

from benchmark.layer_metrics._span_seconds import per

COUNTERS, read = per("glm.fit", "glm.build")
