"""Median fenced wall time of one fused decode-rollout+update dispatch of the token PPO path (ms)."""

from chipbench.window import median


def read(ctx):
    name = "ppo_recurrent.anakin_phase"
    return median([(c.t1 - c.t0) * 1e3 for c in ctx["calls"] if c.name == name])
