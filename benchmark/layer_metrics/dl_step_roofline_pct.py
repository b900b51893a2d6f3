"""The DeepLearning minibatch step's share of its roofline: the least time
the chip could take for the steps of the traced call (the configuration's
``needed_work``, ``dl_step`` part: one step's FLOPs and bytes, and the steps
an epoch), over the device time of the loops that ran them.

The trace names operations by their HLO text, not by the program's scopes,
so a step loop is found by what it holds: a ``while`` event with an
operation inside whose result has the minibatch's rows (``f32[100,300]``,
the forward's activations), and no such ``while`` inside it. The loops' time
is the union of those events: it holds the steps' operations and the gaps
between them inside the loop, and not the epoch's row gather (``ph_dl_perm``,
outside the step loop) nor the metrics' forward (blocks of 65,536 rows)."""

import bisect
import re

from benchmark.harness.peaks import least_seconds
from benchmark.harness.trace import union_ns

WHILE = re.compile(r"^%?while[.\d]* ")


def step_loop_seconds(trace: dict, batch: int) -> tuple[float, int] | None:
    """Seconds of the step loops, averaged over the chips, and the loops
    found on the first chip; None where no loop holds a minibatch."""
    rows = re.compile(rf"^%?\S+ = [a-z]+\d*\[{batch}[,\]]")
    per, found = [], None
    for evs in trace["device"].values():
        loops = [(s, s + d) for name, s, d in evs if WHILE.match(name)]
        if not loops:
            continue
        starts = sorted(s for name, s, _ in evs if rows.match(name))
        holding = [(a, b) for a, b in loops
                   if bisect.bisect_left(starts, a) < bisect.bisect_right(starts, b)]
        inner = [(a, b) for a, b in holding
                 if not any(a <= c and e <= b and (c, e) != (a, b) for c, e in holding)]
        if inner:
            per.append(union_ns((a, b - a) for a, b in inner))
            found = len(inner) if found is None else found
    if not per:
        return None
    return sum(per) / len(per) / 1e9, found


def read(ctx):
    if ctx["peaks"] is None:
        return None
    cfg = ctx["cfg"]
    got = step_loop_seconds(ctx["trace"], int(cfg["estimator"]["mini_batch_size"]))
    if got is None:
        return None
    spent, loops = got
    work = ctx["config"].needed_work(cfg, ctx["call"]["passes"])
    step, steps = work["dl_step"], work["dl_steps"]
    least, bound = least_seconds(step, ctx["peaks"])
    ctx["log"](f"dl_step_roofline_pct: a step needs {step['flops']:.4g} FLOPs and "
               f"{step['bytes']:.4g} bytes, at least {least * 1e6:.4f} us, bound by "
               f"{bound}; {steps:.0f} steps took {spent:.4f} s in {loops} loops")
    return 100.0 * least * steps / spent
