"""PPO losses (reference: sheeprl/algos/ppo/loss.py:1-75), pure jittable fns."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def masked_mean(x: jax.Array, mask: jax.Array) -> jax.Array:
    return (x * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def _reduce(x: jax.Array, reduction: str, mask: Optional[jax.Array] = None) -> jax.Array:
    """``mask`` (1 where a step counts, 0 where it does not) weighs the mean and the sum."""
    if mask is not None:
        if reduction == "mean":
            return masked_mean(x, mask)
        x = x * mask
    if reduction == "mean":
        return x.mean()
    if reduction == "sum":
        return x.sum()
    if reduction == "none":
        return x
    raise ValueError(f"Unknown reduction '{reduction}'")


def policy_loss(
    new_logprobs: jax.Array,
    old_logprobs: jax.Array,
    advantages: jax.Array,
    clip_coef: jax.Array,
    reduction: str = "mean",
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    ratio = jnp.exp(new_logprobs - old_logprobs)
    surr1 = advantages * ratio
    surr2 = advantages * jnp.clip(ratio, 1.0 - clip_coef, 1.0 + clip_coef)
    return _reduce(-jnp.minimum(surr1, surr2), reduction, mask)


def value_loss(
    new_values: jax.Array,
    old_values: jax.Array,
    returns: jax.Array,
    clip_coef: jax.Array,
    clip_vloss: bool,
    reduction: str = "mean",
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    # scale parity with the reference (reference: sheeprl/algos/ppo/loss.py:45-61):
    # the unclipped branch is a PLAIN mse (no 0.5) honoring `reduction`; the
    # clipped branch is ALWAYS 0.5·mean(max(unclipped, clipped)) — the
    # reference ignores `reduction` there, and users porting reference
    # configs rely on the effective vf_coef scale matching exactly
    if not clip_vloss:
        return _reduce((new_values - returns) ** 2, reduction, mask)
    v_clipped = old_values + jnp.clip(new_values - old_values, -clip_coef, clip_coef)
    losses = jnp.maximum((new_values - returns) ** 2, (v_clipped - returns) ** 2)
    return 0.5 * (losses.mean() if mask is None else masked_mean(losses, mask))


def entropy_loss(entropy: jax.Array, reduction: str = "mean", mask: Optional[jax.Array] = None) -> jax.Array:
    return _reduce(-entropy, reduction, mask)
