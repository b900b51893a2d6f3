"""Host seconds of the binning spans for each ``train()`` of the traced call:
``tree.fit_bins`` (the quantile sample and the pull of the edges) plus
``tree.bin_frame`` (the enqueue of the binning pass, or the cache hit). The
pass itself runs on the device after the span has closed: its device time is
under the scope ``ph_bin`` of the trace, not here (``span_seconds``
histogram, differences over the call)."""

SPANS = ("span_seconds_sum{name=tree.fit_bins}", "span_seconds_sum{name=tree.bin_frame}")
FITTED = "span_seconds_count{name=tree.fit_bins}"
CALLS = "span_seconds_count{name=train}"
COUNTERS = (*SPANS, FITTED, CALLS)


def read(ctx):
    c = ctx["call"]["counters"]
    if not c.get(FITTED) or not c.get(CALLS):  # no such spans, or metrics off
        return None
    return sum(c[s] for s in SPANS) / c[CALLS]
