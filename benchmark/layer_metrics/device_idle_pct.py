"""Share of the traced call's wall in which no operation ran on the device:
1 - (union of the device-operation intervals / the call's wall)."""


def read(ctx):
    if ctx["busy_s"] is None:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["call"]["wall_s"])
