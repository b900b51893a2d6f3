"""Device busy time of the traced call for each IRLS iteration it ran
(registry counter, as a difference over the call)."""

COUNTERS = ("glm_irls_iterations_total",)


def read(ctx):
    iters = ctx["call"]["counters"].get("glm_irls_iterations_total")
    if ctx["busy_s"] is None or not iters:
        return None
    return 1e3 * ctx["busy_s"] / iters
