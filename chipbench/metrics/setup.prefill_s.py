"""Seconds of the token cells' warm start (s): ``setup.prefill`` (``ppo_recurrent._warm_start``: the episodes so far drawn
and run through the caches in chunks), less the compile inside it, which ``setup.compile_s`` has."""

from chipbench.harness import load_module


def read(ctx):
    parts = load_module("metrics", "setup.compile_s").account(ctx)
    return None if parts is None else parts["prefill_s"]
