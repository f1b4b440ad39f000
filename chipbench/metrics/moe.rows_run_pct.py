"""Share of the sorted (token, expert) rows that the updates' grouped products visited (%): ``moe_rows_run`` over
``moe_rows_all``, as the program counts them on ``stats.pull`` (the pairs routed to the experts held here, in whole
tiles of the grouped product, over ``tokens x k`` a pass over an expert layer).  Nothing where the program counts
neither (a checkout from before PR 37)."""

from chipbench import spanlog


def read(ctx):
    log = spanlog.records()
    if log is None:
        return None
    counts = [r.counts or {} for r in spanlog.in_window(log, ("stats.pull",), ctx)]
    rows = sum(c.get("moe_rows_all", 0) for c in counts)
    return 100.0 * sum(c.get("moe_rows_run", 0) for c in counts) / rows if rows else None
