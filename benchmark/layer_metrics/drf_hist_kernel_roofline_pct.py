"""The Pallas histogram kernel's share of its roofline in its deep regime
(full node tiles, the saturated ``while_loop``): the least time the chip
could take for the histogram levels of the traced call (the configuration's
``needed_work``, ``hist_kernel`` part: the in-bag rows' bytes), over the
summed device time of the kernel's events in the trace."""

from benchmark.harness.peaks import least_seconds
from benchmark.harness.trace import kernel_seconds

KERNEL = r"^%?hist_pallas"  # "%hist_pallas_dense.66 = ... custom-call(...)"


def read(ctx):
    if ctx["peaks"] is None:
        return None
    spent = kernel_seconds(ctx["trace"], KERNEL)
    if spent is None:
        return None
    work = ctx["config"].needed_work(ctx["cfg"], ctx["call"]["passes"])["hist_kernel"]
    least, bound = least_seconds(work, ctx["peaks"])
    ctx["log"](f"drf_hist_kernel_roofline_pct: needs {work['bytes']:.4g} bytes and "
               f"{work['flops']:.4g} FLOPs, at least {least:.6f} s, bound by "
               f"{bound}; the kernel's events took {spent:.6f} s")
    return 100.0 * least / spent
