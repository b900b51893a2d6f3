"""Device milliseconds of the categorical Gram kernel (``cat_gram_pallas``)
in the traced call for each IRLS iteration the call ran (registry counter,
as a difference over the call). The fit's first Gram pass (the one that
sets lambda's scale) is in the kernel's time too. Nothing to read where the
trace has no such kernel."""

from benchmark.harness.trace import kernel_seconds

from .glm_cat_gram_roofline_pct import KERNEL

COUNTERS = ("glm_irls_iterations_total",)


def read(ctx):
    iters = ctx["call"]["counters"].get("glm_irls_iterations_total")
    spent = kernel_seconds(ctx["trace"], KERNEL)
    if not iters or spent is None:
        return None
    return 1e3 * spent / iters
