"""Medians and spreads of the runs that ``sets.sh`` recorded: a spread is
the distance between the first and third quartile as a share of the median."""

import json
import statistics as st
import sys


def spread(v):
    q = st.quantiles(v, n=4)
    return (q[2] - q[0]) / st.median(v)


rows = [json.loads(line) for line in open(sys.argv[1])]
for r in rows:
    L = r["line"]
    if L is None:
        print("NO LINE", r["seed"], r["rc"])
        continue
    print(r.get("set"), r["seed"], L["correct"], L["attempted"], r.get("wall_s"),
          {k: round(v["value"], 4) for k, v in L["metrics"].items()},
          {k: float(f"{c['value']:.3g}") for k, c in L["checks"].items()},
          {k: v for k, v in L["device"].items() if k.endswith("_bytes") or k.endswith("_s")})
for metric in ("train_rows_per_s", "setup_s"):
    for s in (1, 2):
        v = [r["line"]["metrics"][metric]["value"] for r in rows
             if r.get("set") == s and r["line"] and metric in r["line"]["metrics"]]
        if len(v) >= 2:
            print(metric, "set", s, "n", len(v), "median", st.median(v),
                  "spread", round(spread(v), 5), "min", min(v), "max", max(v))
