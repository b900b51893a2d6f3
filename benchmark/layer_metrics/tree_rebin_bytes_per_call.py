"""Bytes the binning passes of the traced call moved, for each ``train()``:
the program's own tally ``tree_hist_hbm_bytes_total{path=rebin}`` (one f32
read and one u8 write for every cell of the padded frame; a hit in the code
cache moves nothing), as a difference over the call. 0 is a reading here:
a call that binned nothing."""

BYTES = "tree_hist_hbm_bytes_total{path=rebin}"
CALLS = "span_seconds_count{name=train}"  # says that flat names resolve at all
COUNTERS = (BYTES, CALLS)


def read(ctx):
    c = ctx["call"]["counters"]
    if not c.get(CALLS):  # a program without the flat-name bridge reads 0 for both
        return None
    return c[BYTES] / c[CALLS]
