"""Seconds the run spent building itself (s): the ``setup.*`` children of the program's ``setup`` span other than
``setup.prefill`` (compose, registry, backend, logger, envs, agent, optimizer, ring, a resumed checkpoint, a first
``import jax``), less the compile inside them, which ``setup.compile_s`` has."""

from chipbench.harness import load_module


def read(ctx):
    parts = load_module("metrics", "setup.compile_s").account(ctx)
    return None if parts is None else parts["build_s"]
