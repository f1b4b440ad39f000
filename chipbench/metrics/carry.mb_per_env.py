"""Megabytes of recurrent carry one env holds (attention caches, convolution windows, its position): the
``carry_bytes`` the program counts on the window's ``stats.pull`` spans.  Nothing where the program counts
none (a checkout from before PR 34)."""

from chipbench import spanlog
from chipbench.window import median


def read(ctx):
    log = spanlog.records()
    if log is None:
        return None
    counts = [r.counts or {} for r in spanlog.in_window(log, ("stats.pull",), ctx)]
    value = median([c["carry_bytes"] for c in counts if "carry_bytes" in c])
    return None if value is None else value / 1e6
