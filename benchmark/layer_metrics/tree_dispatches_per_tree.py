"""Host dispatches of the tree builder for each tree built, from the
program's registry counters, as differences over the traced call."""

COUNTERS = ("tree_dispatches_total", "tree_trees_built_total")


def read(ctx):
    c = ctx["call"]["counters"]
    if not c.get("tree_trees_built_total"):
        return None
    return c["tree_dispatches_total"] / c["tree_trees_built_total"]
