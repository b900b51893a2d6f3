"""Levels of the saturated ``while_loop`` (the frontier pinned at the node
cap) that ran for each tree built, from the program's registry counters, as
differences over the traced call: 9 for a depth-20 tree while its frontier
stays full; a loop that ends early shows here."""

COUNTERS = ("tree_sat_levels_total", "tree_trees_built_total")


def read(ctx):
    c = ctx["call"]["counters"]
    if not c.get("tree_trees_built_total"):
        return None
    return c["tree_sat_levels_total"] / c["tree_trees_built_total"]
