"""The categorical Gram kernel's share of its roofline: the least time the
chip could take for the Gram passes the traced call needs (the
configuration's ``needed_work``, ``cat_gram`` part: the codes, numerics,
weights and working responses read, and the FLOPs of the 45 upper-triangle
Gram entries and 9 ``b`` entries a row), over the summed device time of the
kernel's events in the trace. Nothing to read where the trace has no such
kernel or the configuration counts no such part."""

from benchmark.harness.peaks import least_seconds
from benchmark.harness.trace import kernel_seconds

KERNEL = r"^%?cat_gram_pallas"  # the kernel's events: "%cat_gram_pallas.1 = (...) custom-call(...)"


def read(ctx):
    if ctx["peaks"] is None:
        return None
    spent = kernel_seconds(ctx["trace"], KERNEL)
    work = ctx["config"].needed_work(ctx["cfg"], ctx["call"]["passes"]).get("cat_gram")
    if spent is None or work is None:
        return None
    least, bound = least_seconds(work, ctx["peaks"])
    ctx["log"](f"glm_cat_gram_roofline_pct: needs {work['bytes']:.4g} bytes and "
               f"{work['flops']:.4g} FLOPs, at least {least:.6f} s, bound by "
               f"{bound}; the kernel's events took {spent:.6f} s")
    return 100.0 * least / spent
