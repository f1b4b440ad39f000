"""What the env step waits for the device in the real pipeline (ms): the median ``env.step`` span of
the unfenced stretch after the window, less its median inside the fenced window, not under 0.  The
stretch is the 3 s the profiler records (about 13 DV3 iterations, each 7 to 10% longer for the
recording): the wait is read with that on it."""

from chipbench import spanlog
from chipbench.window import median


def read(ctx):
    log = spanlog.records()
    if log is None:
        return None
    unfenced = median([spanlog.ms(r) for r in spanlog.in_stretch(log, ("env.step",), ctx)])
    if unfenced is None:
        return 0.0
    fenced = median([spanlog.ms(r) for r in spanlog.in_window(log, ("env.step",), ctx)]) or 0.0
    return max(unfenced - fenced, 0.0)
