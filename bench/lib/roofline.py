"""The least time a chip could take for the bytes and FLOPs a program
needs.  What a block needs is its architecture's to say
(bench/archs/<arch>/needs.py, named by the configuration's ``arch``); the
peaks are lib/peaks.py's."""

from __future__ import annotations


def least_seconds(need: dict, peaks: dict) -> dict:
    """The least time the chip could take and which bound sets it."""
    t_mem = need["bytes"] / peaks["hbm_bytes_per_s"]
    t_flop = need["flops"] / peaks["bf16_flops"]
    return {"seconds": max(t_mem, t_flop),
            "bound": "memory" if t_mem >= t_flop else "compute",
            "t_mem": t_mem, "t_flop": t_flop}
