"""Seconds of one program span for each occurrence of another, from the
program's ``span_seconds`` histogram through ``metrics.counter_value``'s
flat names, as differences over the traced call: what the ``glm_*_s_per_fit``
readers share."""


def per(span: str, unit_span: str):
    """``(COUNTERS, read)`` of a reader: the seconds of ``span`` divided by
    the times ``unit_span`` ran. Nothing where ``unit_span`` never ran: a
    program without these spans, or one with its metrics gated off, reads 0.0
    for every flat name."""
    total = f"span_seconds_sum{{name={span}}}"
    units = f"span_seconds_count{{name={unit_span}}}"

    def read(ctx):
        c = ctx["call"]["counters"]
        return c[total] / c[units] if c.get(units) else None

    return (total, units), read
