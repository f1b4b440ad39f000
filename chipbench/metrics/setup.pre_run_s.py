"""Seconds from the process's start, as the OS has it, to ``cli.run``'s entry (s): the interpreter, ``import jax``, the
benchmark's own start (an ``execv`` included).  The count ``pre_run_ms`` on the program's ``setup`` span, over 1e3; nothing
where the platform gave the program no start time, or the log holds no ``setup`` record."""

from chipbench.harness import load_module


def read(ctx):
    parts = load_module("metrics", "setup.compile_s").account(ctx)
    return None if parts is None else parts["pre_run_s"]
