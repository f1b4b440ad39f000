"""Median fenced wall time of the pull of fresh weights to the player before a train dispatch (ms)."""

from chipbench.window import median


def read(ctx):
    return median([(c.t1 - c.t0) * 1e3 for c in ctx["calls"] if c.name == "player.sync"])
