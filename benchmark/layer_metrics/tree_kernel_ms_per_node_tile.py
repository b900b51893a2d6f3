"""What a node tile costs: the summed device time of the histogram kernel's
events in the traced call over the node tiles (64 node slots, one pass over
the rows each) the tree programs asked for in that call, from the program's
registry counter. Nothing to read where the program has no such counter."""

from benchmark.harness.trace import kernel_seconds

from .drf_hist_kernel_roofline_pct import KERNEL

COUNTERS = ("tree_node_tiles_total",)


def read(ctx):
    tiles = ctx["call"]["counters"].get("tree_node_tiles_total")
    spent = kernel_seconds(ctx["trace"], KERNEL)
    if not tiles or spent is None:
        return None
    ctx["log"](f"tree_kernel_ms_per_node_tile: {spent:.6f} s of kernel events "
               f"over {tiles:.0f} node tiles")
    return 1e3 * spent / tiles
