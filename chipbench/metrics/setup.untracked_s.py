"""Seconds of the program's ``setup`` span that none of its ``setup.*`` children covers and that is no compile (s):
where it is large, a layer of set-up is missing its span."""

from chipbench.harness import load_module


def read(ctx):
    parts = load_module("metrics", "setup.compile_s").account(ctx)
    return None if parts is None else parts["untracked_s"]
