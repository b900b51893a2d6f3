"""Seconds of the span ``model.score_metrics`` (the predict pass, its pull to
the host and the metric tables built there) for each GLM fit of the traced
call."""

from benchmark.layer_metrics._span_seconds import per

COUNTERS, read = per("model.score_metrics", "glm.build")
