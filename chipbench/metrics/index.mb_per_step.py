"""Megabytes of index keys that one decode step of all the envs scores: the ``index_bytes`` the program counts on the
window's ``stats.pull`` spans (envs x sparse layers x the positions each env's episode had written x the bytes of
one index key, from the rollout's positions as ``cache_read`` is counted) over the decode steps those dispatches
made (``steps`` counts env steps: envs x decode steps).  It is the byte count a roofline of the indexer's scores
would divide by.  Nothing where the program counts none (a checkout older than the sparse layer, or a model without
one)."""

from chipbench import spanlog


def read(ctx):
    log = spanlog.records()
    if log is None:
        return None
    counts = [r.counts or {} for r in spanlog.in_window(log, ("stats.pull",), ctx)]
    counts = [c for c in counts if "index_bytes" in c and c.get("steps")]
    if not counts:
        return None
    decode_steps = sum(c["steps"] for c in counts) / ctx["cfg"]["env"]["num_envs"]
    return sum(c["index_bytes"] for c in counts) / decode_steps / 1e6
