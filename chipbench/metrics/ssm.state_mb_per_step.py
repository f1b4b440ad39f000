"""Megabytes of state-space state and convolution window that one decode step of all the envs reads and writes
again: the ``ssm_state_bytes`` the program counts on the window's ``stats.pull`` spans (envs x Mamba-2 layers
x 2 x the bytes of one state and window x decode steps, from the carry's shapes and dtypes) over the decode
steps those dispatches made (``steps`` counts env steps: envs x decode steps).  It is the byte count a roofline
of the state update would divide by.  Nothing where the program counts none (a checkout from before PR 36, or
a model without such a layer)."""

from chipbench import spanlog


def read(ctx):
    log = spanlog.records()
    if log is None:
        return None
    counts = [r.counts or {} for r in spanlog.in_window(log, ("stats.pull",), ctx)]
    counts = [c for c in counts if "ssm_state_bytes" in c and c.get("steps")]
    if not counts:
        return None
    decode_steps = sum(c["steps"] for c in counts) / ctx["cfg"]["env"]["num_envs"]
    return sum(c["ssm_state_bytes"] for c in counts) / decode_steps / 1e6
