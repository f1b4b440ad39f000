"""Median fenced wall time of one player step, the copy of its action to the host included (ms)."""

from chipbench.window import median


def read(ctx):
    return median([(c.t1 - c.t0) * 1e3 for c in ctx["calls"] if c.name.endswith(".player_step")])
