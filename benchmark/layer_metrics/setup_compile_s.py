"""Seconds that ``train()`` calls spent in XLA compiles and persistent-cache
loads (jax's ``backend_compile_duration``, which times both): with a warm
cache the loads alone, in a cold one most of set-up. Process totals under the
root span ``train``, read when the reader runs, as ``setup_lower_s``."""

from benchmark.layer_metrics import _compile_pipeline as _cp


def read(ctx):
    return _cp.total(_cp.SECONDS, ("compile",))
