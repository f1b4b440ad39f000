"""Median wall time of the vector env's step, all envs of one loop step, in the fenced window (ms):
the env layer's own cost with the device drained (the program's ``env.step`` span)."""

from chipbench import spanlog
from chipbench.window import median


def read(ctx):
    log = spanlog.records()
    if log is None:
        return None
    return median([spanlog.ms(r) for r in spanlog.in_window(log, ("env.step",), ctx)]) or 0.0
