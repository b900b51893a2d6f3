"""Seconds of the span ``glm.datainfo`` for each GLM fit of the traced call:
``DataInfo.fit``, the design matrix, the response and weight lanes, to the
pull of ``nobs``."""

from benchmark.layer_metrics._span_seconds import per

COUNTERS, read = per("glm.datainfo", "glm.build")
