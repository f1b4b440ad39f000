"""Share of the attention caches' positions that the window's decode steps fetched (%): ``cache_read`` over
``cache_held``, as the program counts them on ``stats.pull`` (whole blocks of a layer read as far as each env has
written it, all of a layer read whole).  Nothing where the program counts neither (a checkout from before PR 35)."""

from chipbench import spanlog


def read(ctx):
    log = spanlog.records()
    if log is None:
        return None
    counts = [r.counts or {} for r in spanlog.in_window(log, ("stats.pull",), ctx)]
    held = sum(c.get("cache_held", 0) for c in counts)
    return 100.0 * sum(c.get("cache_read", 0) for c in counts) / held if held else None
