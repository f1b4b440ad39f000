"""Host time per loop iteration of the window in the loop's named stalls (ms): the metric flush
(``log.flush``), the health sentinel's poll (``health.poll``) and the caller-thread part of a
checkpoint (``ckpt.save``), summed over the window and divided by its iterations.  Listed for the
cells whose window holds such stalls (the Anakin cell: a flush every dispatch, a save every second).
Not for ``dv3s_forage_coupled``: its flush comes once in 5000 steps, and its sentinel's poll waits
for the device, which a traced window has drained at every probed call (0.06 ms read there)."""

from chipbench import spanlog

STALLS = ("log.flush", "health.poll", "ckpt.save")


def read(ctx):
    log = spanlog.records()
    if log is None:
        return None
    return sum(spanlog.ms(r) for r in spanlog.in_window(log, STALLS, ctx)) / max(spanlog.window_of(ctx)[2], 1)
