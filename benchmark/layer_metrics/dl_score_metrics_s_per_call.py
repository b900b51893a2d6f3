"""Seconds of the span ``model.score_metrics`` (the training metrics: the
blocked forward over the training design and the device statistics, or
the host path) for each ``train()`` call of the traced call."""

from benchmark.layer_metrics._span_seconds import per

COUNTERS, read = per("model.score_metrics", "train")
