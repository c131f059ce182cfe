"""Layer metrics over the window delta of ``GET /genperf``."""

from lib.formula import evaluate


def read(metric: dict, ctx: dict):
    return evaluate(metric["formula"], ctx["genperf_before"],
                    ctx["genperf_after"], ctx["harness"])
