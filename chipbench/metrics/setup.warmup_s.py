"""Seconds from the end of the program's ``setup`` span (the loop's first iteration) to the window's opening (s), less
the compile inside them, which ``setup.compile_s`` has: DV3's ``learning_starts`` steps, every cell's three warm-up
dispatches and what the harness does before it opens the window."""

from chipbench.harness import load_module


def read(ctx):
    parts = load_module("metrics", "setup.compile_s").account(ctx)
    return None if parts is None else parts["warmup_s"]
