"""Layer metrics from the profiler trace: a program's share of its
roofline.  ``ctx["trace"]`` is lib/trace_reduce.py's reduction; the needed
bytes and FLOPs come from the ``needs.py`` of the block the configuration
names (archs/<arch>/), the harness's own record of what was in flight
during the traced span and the window's ``/genperf`` deltas."""

from lib import roofline
from lib.formula import deltas
from lib.manifest import arch_module
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
    needs = arch_module(ctx["bench_dir"], ctx["config"], "needs")
    counters = deltas(ctx.get("genperf_before") or {},
                      ctx.get("genperf_after") or {})
    span = ctx["traced"]          # the harness's account of the traced span
    if f["program"] == "decode":
        steps = prog["calls"] * ctx["deployment"]["span"]
        if not steps or not span["decode_rows_mean"]:
            return None
        need = needs.decode_step(ctx["config"], span["decode_rows_mean"],
                                 span["decode_live_positions_mean"],
                                 counters)
        least = roofline.least_seconds(need, peaks)
        share = 100.0 * least["seconds"] * steps / prog["seconds"]
    else:
        if not span["prefill_tokens"]:
            return None
        need = needs.prefill(ctx["config"], prog["calls"],
                             span["prefill_tokens"],
                             span["prefill_attended"], counters)
        least = roofline.least_seconds(need, peaks)
        share = 100.0 * least["seconds"] / prog["seconds"]
    ctx.setdefault("bounds", {})[metric["name"]] = least["bound"]
    return share
