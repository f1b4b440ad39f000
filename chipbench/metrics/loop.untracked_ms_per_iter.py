"""Median over the window's iterations of the host time no span owns (ms): the ``iter`` span's
duration less what its child spans on its own thread cover (work another thread does for the
iteration runs beside it).  Where it is large, a layer is missing its span."""

from chipbench import spanlog
from chipbench.window import median


def read(ctx):
    log = spanlog.records()
    if log is None:
        return None
    iters = spanlog.in_window(log, ("iter",), ctx)
    covered = {r.id: 0.0 for r in iters}
    thread = {r.id: r.thread for r in iters}
    for r in log:
        if r.parent in covered and r.thread == thread[r.parent]:
            covered[r.parent] += spanlog.ms(r)
    return median([max(spanlog.ms(r) - covered[r.id], 0.0) for r in iters]) or 0.0
