"""Host seconds that ``train()`` calls spent tracing their programs to jaxprs
and lowering them to MLIR (jax's ``jaxpr_trace_duration`` and
``jaxpr_to_mlir_module_duration``, a stage nested in another counted once):
Python-side work that paces a first call whatever the compile cache holds.
Process totals under the root span ``train``, read when the reader runs: the
warm-up call's, plus what the window's calls added (``compile_s_per_call``
says how much)."""

from benchmark.layer_metrics import _compile_pipeline as _cp


def read(ctx):
    return _cp.total(_cp.SECONDS, ("trace", "lower"))
