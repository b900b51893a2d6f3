"""The whole step's share of the chip's peak, end to end: the least time the
chip could take for the work the algorithm NEEDS in the traced call (the
configuration's ``needed_work`` of its shapes: max of FLOPs / peak FLOP/s and
bytes / peak bytes/s), over the call's wall on the host's clock (a whole
``train`` call, seconds long). Nothing of the trace enters it: the numerator
comes from the shapes and the table of peaks, the denominator from the clock
around the call. Says which bound binds on an earlier line."""

from benchmark.harness.peaks import least_seconds


def read(ctx):
    if ctx["peaks"] is None:
        return None
    work = ctx["config"].needed_work(ctx["cfg"], ctx["call"]["passes"])
    least, bound = least_seconds(work, ctx["peaks"])
    ctx["log"](f"train_step_mfu_pct: needs {work['flops']:.4g} FLOPs and "
               f"{work['bytes']:.4g} bytes, at least {least:.6f} s on this chip, "
               f"bound by {bound}; the call took {ctx['call']['wall_s']:.3f} s")
    return 100.0 * least / ctx["call"]["wall_s"]
