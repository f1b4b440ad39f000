"""Share of the window's decode steps at a position beyond the sliding window (%), where a window layer's ring
and the full layer's cache differ: ``beyond_window`` over ``steps``, as the program counts them on ``stats.pull``."""

from chipbench import spanlog


def read(ctx):
    log = spanlog.records()
    if log is None:
        return None
    counts = [r.counts or {} for r in spanlog.in_window(log, ("stats.pull",), ctx)]
    steps = sum(c.get("steps", 0) for c in counts)
    return 100.0 * sum(c.get("beyond_window", 0) for c in counts) / steps if steps else None
