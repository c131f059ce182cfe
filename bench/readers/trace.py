"""Layer metrics from the profiler trace: a program's share of its
roofline.  ``ctx["trace"]`` is lib/trace_reduce.py's reduction; the needed
bytes and FLOPs come from lib/roofline.py and the harness's own record of
what was in flight during the traced span."""

from lib import roofline
from lib.peaks import peaks_for


def read(metric: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace:
        return None
    f = metric["formula"]
    prog = trace["programs"].get(f["program"])
    if not prog or prog["seconds"] <= 0:
        return None
    peaks = peaks_for(ctx["device"]["kind"])
    span = ctx["traced"]          # the harness's account of the traced span
    if f["program"] == "decode":
        steps = prog["calls"] * ctx["deployment"]["span"]
        if not steps or not span["decode_rows_mean"]:
            return None
        need = roofline.decode_step(ctx["config"], span["decode_rows_mean"],
                                    span["decode_live_positions_mean"])
        least = roofline.least_seconds(need, peaks)
        share = 100.0 * least["seconds"] * steps / prog["seconds"]
    else:
        if not span["prefill_tokens"]:
            return None
        need = roofline.prefill(ctx["config"], prog["calls"],
                                span["prefill_tokens"],
                                span["prefill_attended"])
        least = roofline.least_seconds(need, peaks)
        share = 100.0 * least["seconds"] / prog["seconds"]
    ctx.setdefault("bounds", {})[metric["name"]] = least["bound"]
    return share
