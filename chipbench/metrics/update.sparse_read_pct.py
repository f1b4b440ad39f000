"""Share of the sparse layers' prefix that the window's updates read (%): ``segment_read`` over ``segment_held``, as
the program counts them on ``stats.pull`` (positions of the carried prefix that ``ops/segment_attention.py`` fetched,
in whole blocks of an env that some query selected from, over the positions each pass's envs held, summed over the
sparse layers and the updates' passes).  Nothing where the program counts neither (a checkout from before these
counts, or a model whose sparse layers keep the masked product)."""

from chipbench import spanlog


def read(ctx):
    log = spanlog.records()
    if log is None:
        return None
    counts = [r.counts or {} for r in spanlog.in_window(log, ("stats.pull",), ctx)]
    held = sum(c.get("segment_held", 0) for c in counts)
    return 100.0 * sum(c.get("segment_read", 0) for c in counts) / held if held else None
