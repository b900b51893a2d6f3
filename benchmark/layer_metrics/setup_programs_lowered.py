"""Programs that ``train()`` calls lowered to MLIR (one ``jaxpr_to_mlir_module``
event each, under the root span ``train``): an exact count of the set-up's
programs, free of the host's speed. Process totals, read when the reader runs,
as ``setup_lower_s``."""

from benchmark.layer_metrics import _compile_pipeline as _cp


def read(ctx):
    return _cp.total(_cp.EVENTS, ("lower",))
