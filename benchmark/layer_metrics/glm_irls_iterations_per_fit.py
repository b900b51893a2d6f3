"""IRLS iterations the program took for each fit of the traced call
(registry counter, as a difference over the call). The end-to-end rate
counts fits, not iterations: this is where the program's own count shows."""

COUNTERS = ("glm_irls_iterations_total",)


def read(ctx):
    iters = ctx["call"]["counters"].get("glm_irls_iterations_total")
    if not iters or not ctx["call"]["passes"]:
        return None
    return iters / ctx["call"]["passes"]
