"""Median fenced wall time of one gradient update: a train dispatch over the updates it made (ms)."""

from chipbench.window import median


def read(ctx):
    return median([
        (c.t1 - c.t0) * 1e3 / c.work["updates"]
        for c in ctx["calls"] if c.name.endswith(".train_phase_device") and c.work.get("updates")
    ])
