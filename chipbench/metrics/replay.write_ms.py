"""Median fenced wall time of one ``DeviceReplay.add``: staging the rows and the donated ring write (ms)."""

from chipbench.window import median


def read(ctx):
    return median([(c.t1 - c.t0) * 1e3 for c in ctx["calls"] if c.name == "replay.add"])
