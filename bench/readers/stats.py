"""Layer metrics over the window delta of ``GET /stats``."""

from lib.formula import evaluate


def read(metric: dict, ctx: dict):
    return evaluate(metric["formula"], ctx["stats_before"],
                    ctx["stats_after"], ctx["harness"])
