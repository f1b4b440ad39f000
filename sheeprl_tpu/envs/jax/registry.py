"""Registry + config plumbing for the pure-JAX env family.

``env=jax_*`` Hydra groups set ``env.wrapper.kind: jax`` plus a registry
``id``; :func:`jax_env_from_cfg` builds the env from there.  Two consumers:

* the :class:`~sheeprl_tpu.envs.jax.adapter.JaxToGymAdapter` path
  (``utils/env.py``), which lets EVERY existing algo loop run these envs
  unmodified through the current vector-env machinery, and
* the Anakin fused-rollout path (``envs/jax/anakin.py``), which the
  on-policy loops (ppo, a2c) select via :func:`anakin_enabled` to step the
  batched env INSIDE the compiled update.
"""

from __future__ import annotations

from typing import Any, Callable, Dict

from sheeprl_tpu.envs.jax.core import JaxEnv

JAX_ENVS: Dict[str, Callable[..., JaxEnv]] = {}


def _register(name: str):
    def deco(builder: Callable[..., JaxEnv]):
        JAX_ENVS[name] = builder
        return builder

    return deco


@_register("cartpole")
def _cartpole(**kwargs: Any) -> JaxEnv:
    from sheeprl_tpu.envs.jax.cartpole import JaxCartPole

    return JaxCartPole(**kwargs)


@_register("pendulum")
def _pendulum(**kwargs: Any) -> JaxEnv:
    from sheeprl_tpu.envs.jax.pendulum import JaxPendulum

    return JaxPendulum(**kwargs)


@_register("forage")
def _forage(**kwargs: Any) -> JaxEnv:
    from sheeprl_tpu.envs.jax.forage import JaxForage

    return JaxForage(**kwargs)


@_register("multiroom")
def _multiroom(**kwargs: Any) -> JaxEnv:
    from sheeprl_tpu.envs.jax.multiroom import JaxMultiRoom

    return JaxMultiRoom(**kwargs)


@_register("tokens")
def _tokens(**kwargs: Any) -> JaxEnv:
    from sheeprl_tpu.envs.jax.tokens import JaxTokens

    return JaxTokens(**kwargs)


def make_jax_env(env_id: str, **kwargs: Any) -> JaxEnv:
    """Build a registered pure-JAX env; accepts both the bare registry name
    (``cartpole``) and the config-group spelling (``jax_cartpole``)."""
    name = env_id[4:] if env_id.startswith("jax_") else env_id
    if name not in JAX_ENVS:
        raise ValueError(f"Unknown jax env '{env_id}'; options: {sorted(JAX_ENVS)}")
    return JAX_ENVS[name](**kwargs)


def is_jax_native(cfg: Any) -> bool:
    """True when the selected env group is a pure-JAX env (wrapper kind)."""
    wrapper = cfg.env.get("wrapper") or {}
    return isinstance(wrapper, dict) and wrapper.get("kind") == "jax"


def jax_env_from_cfg(cfg: Any) -> JaxEnv:
    """Build the configured jax env (wrapper kwargs pass through to the
    registered constructor, like every other suite wrapper)."""
    wrapper = dict(cfg.env.get("wrapper") or {})
    env_id = wrapper.pop("id", None) or cfg.env.id
    wrapper.pop("kind", None)
    # difficulty axis (docs/jax_envs.md): a top-level env.level override
    # reaches every jax env ctor without per-env wrapper plumbing
    if cfg.env.get("level") is not None:
        wrapper.setdefault("level", float(cfg.env.level))
    env = make_jax_env(env_id, **wrapper)
    if cfg.env.get("max_episode_steps"):
        env.max_episode_steps = int(cfg.env.max_episode_steps)
    return env


def anakin_enabled(cfg: Any, fabric: Any) -> bool:
    """Whether an on-policy loop should fuse its rollout (Anakin mode).

    ``algo.anakin``: ``auto`` (default) fuses whenever the env is
    jax-native and the run is single-process; ``True`` demands it (raising
    on a non-jax env); ``False`` forces the adapter/vector-env path even
    for jax envs (the scenario matrix's adapter cells).
    Multi-process runs fall back to the adapter path: the fused program is
    a per-process dispatch and the cross-host rollout-pool semantics of
    the decoupled samplers don't apply to it yet.
    """
    mode = cfg.algo.get("anakin", "auto")
    native = is_jax_native(cfg)
    if isinstance(mode, str) and mode.lower() == "auto":
        wanted = native
    elif bool(mode):
        if not native:
            raise ValueError(
                "algo.anakin=True requires a pure-JAX env (env=jax_*); "
                f"got env.id={cfg.env.id!r}"
            )
        wanted = True
    else:
        return False
    if wanted and fabric.num_processes > 1:
        from sheeprl_tpu.parallel.distributed import rank_zero_warn

        # once, on rank 0 — N processes each printing the same fallback
        # turns a pod launch into a wall of duplicate warnings
        rank_zero_warn(
            "algo.anakin: multi-process run — falling back to the vector-env "
            "adapter path (fused rollouts are single-process for now)",
            key="anakin.multiprocess_fallback",
        )
        return False
    return wanted
