"""Device bytes of the GLM design operands that the traced call's
transforms made, for each row of the configuration and each fit: the
program's own tally ``glm_design_bytes_total`` as a difference over the
call (4 a design column a row for the dense one-hot design; 4 a
categorical column and 4 a dense column a row for the factored one).
Nothing to read where the program keeps no such tally (it reads 0)."""

COUNTERS = ("glm_design_bytes_total",)


def read(ctx):
    made = ctx["call"]["counters"].get("glm_design_bytes_total")
    if not made or not ctx["call"]["passes"]:
        return None
    return made / (float(ctx["cfg"]["rows"]) * ctx["call"]["passes"])
