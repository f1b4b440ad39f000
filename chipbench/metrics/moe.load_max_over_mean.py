"""Tokens of the fullest held expert over the mean held expert's, median over the window's dispatches: the
``moe_load_max`` and ``moe_load_mean`` the program counts on its ``stats.pull`` spans (1: even routing)."""

from chipbench import spanlog
from chipbench.window import median


def read(ctx):
    log = spanlog.records()
    if log is None:
        return None
    counts = [r.counts or {} for r in spanlog.in_window(log, ("stats.pull",), ctx)]
    return median([c["moe_load_max"] / c["moe_load_mean"] for c in counts if c.get("moe_load_mean")])
