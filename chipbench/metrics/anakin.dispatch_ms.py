"""Median fenced wall time of one fused rollout+update dispatch (ms)."""

from chipbench.window import median


def read(ctx):
    name = "ppo.anakin_phase"
    return median([(c.t1 - c.t0) * 1e3 for c in ctx["calls"] if c.name == name])
