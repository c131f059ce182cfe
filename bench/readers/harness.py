"""Layer metrics the load generator measures on itself: a percentile of a
per-request series over the measured set."""

from lib.arith import percentile


def read(metric: dict, ctx: dict):
    f = metric["formula"]
    values = [r[f["series"]] for r in ctx["records"]
              if r.get("measured", True) and r.get(f["series"]) is not None]
    return percentile(values, f["percentile"])
