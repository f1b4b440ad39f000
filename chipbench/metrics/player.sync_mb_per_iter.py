"""Megabytes of player weights pulled to the host per loop iteration of the window: the ``bytes``
the program counts on its ``player.sync`` spans."""

from chipbench import spanlog


def read(ctx):
    log = spanlog.records()
    if log is None:
        return None
    pulled = sum((r.counts or {}).get("bytes", 0) for r in spanlog.in_window(log, ("player.sync",), ctx))
    return pulled / max(spanlog.window_of(ctx)[2], 1) / 1e6
