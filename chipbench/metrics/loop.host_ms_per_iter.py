"""Median over the window's iterations of the wall time outside the probed device calls (ms).

In a traced run every probed call is fenced, so what is left of an iteration is the loop's own
host work: env stepping through the adapter, logging, checkpointing, Python."""

from chipbench.window import median


def read(ctx):
    bounds = ctx["window"].boundaries
    inside = [0.0] * (len(bounds) - 1)
    i = 0
    for call in sorted((c for c in ctx["calls"] if c.device), key=lambda c: c.t0):
        while i < len(inside) - 1 and call.t0 >= bounds[i + 1]:
            i += 1
        inside[i] += call.t1 - call.t0
    host = [(bounds[k + 1] - bounds[k] - inside[k]) * 1e3 for k in range(len(inside))]
    return median(host)
