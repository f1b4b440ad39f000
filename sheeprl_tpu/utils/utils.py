"""Shared math / control utilities (JAX-first).

Capability parity with the reference's grab-bag utils
(reference: sheeprl/utils/utils.py:63-313) — GAE, symlog/symexp, two-hot
encoding, normalization, polynomial decay, the replay-ratio governor — but
every array op is a pure jittable JAX function shaped for ``lax.scan`` /
XLA fusion instead of per-step Python loops.
"""

from __future__ import annotations

import copy
import os
import warnings
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import yaml


# --------------------------------------------------------------------------
# returns / advantages
# --------------------------------------------------------------------------

def gae(
    rewards: jax.Array,
    values: jax.Array,
    dones: jax.Array,
    next_value: jax.Array,
    gamma: float,
    lmbda: float,
) -> Tuple[jax.Array, jax.Array]:
    """Generalized advantage estimation over a ``(T, B, ...)`` rollout.

    The reference computes this with a reversed Python loop
    (reference: sheeprl/utils/utils.py:63-100); here it is a single reversed
    ``lax.scan`` so the whole advantage computation compiles into the rollout
    post-processing graph.

    ``dones[t]`` flags whether the episode ended *at* step t (so state t+1 was
    a reset).  Returns ``(returns, advantages)`` with the same shape as
    ``rewards``.
    """
    not_done = 1.0 - dones.astype(values.dtype)

    def step(carry, xs):
        lastgaelam, next_val = carry
        reward, value, nd = xs
        delta = reward + gamma * next_val * nd - value
        lastgaelam = delta + gamma * lmbda * nd * lastgaelam
        return (lastgaelam, value), lastgaelam

    init = (jnp.zeros_like(next_value), next_value)
    _, advantages = jax.lax.scan(step, init, (rewards, values, not_done), reverse=True)
    returns = advantages + values
    return returns, advantages


def lambda_returns(
    rewards: jax.Array,
    values: jax.Array,
    continues: jax.Array,
    lmbda: float,
) -> jax.Array:
    """TD(λ) returns for imagined trajectories (Dreamer-style).

    ``rewards, values, continues`` are ``(T, B, ...)``; ``continues`` already
    folds in the discount factor (γ·(1-done)).  The recursion
    ``R_t = r_t + c_t · ((1-λ)·v_{t+1} + λ·R_{t+1})`` runs as a reversed
    ``lax.scan`` (reference equivalent: sheeprl/algos/dreamer_v3/utils.py:66-77).
    The last step bootstraps from ``values[-1]``.
    """

    def step(next_ret, xs):
        reward, cont, next_value = xs
        ret = reward + cont * ((1 - lmbda) * next_value + lmbda * next_ret)
        return ret, ret

    next_values = jnp.concatenate([values[1:], values[-1:]], axis=0)
    _, rets = jax.lax.scan(step, values[-1], (rewards, continues, next_values), reverse=True)
    return rets


# --------------------------------------------------------------------------
# symlog / two-hot
# --------------------------------------------------------------------------

def symlog(x: jax.Array) -> jax.Array:
    return jnp.sign(x) * jnp.log1p(jnp.abs(x))


def symexp(x: jax.Array) -> jax.Array:
    return jnp.sign(x) * (jnp.exp(jnp.abs(x)) - 1.0)


def two_hot_encoder(x: jax.Array, support_range: int = 300, num_buckets: Optional[int] = None) -> jax.Array:
    """Symlog two-hot encoding onto a symmetric integer support.

    A scalar ``v`` (after symlog) is split between its two neighboring bucket
    centers with linear weights (reference: sheeprl/utils/utils.py:156-205,
    default 300 range / 601 buckets; DreamerV3 uses its own 255-bin variant
    through TwoHotEncodingDistribution).  Vectorized: no loops, one scatter.
    ``x``: (..., 1) → (..., num_buckets).
    """
    if num_buckets is None:
        num_buckets = int(2 * support_range + 1)
    x = symlog(x)
    # clip INTO the support (reference: sheeprl/utils/utils.py:176 clips the
    # tensor): without it a value below -support splits weight between the
    # first two buckets instead of saturating the first
    x = jnp.clip(x, -support_range, support_range)
    buckets = jnp.linspace(-support_range, support_range, num_buckets, dtype=x.dtype)
    below = jnp.sum((buckets <= x).astype(jnp.int32), axis=-1) - 1
    below = jnp.clip(below, 0, num_buckets - 1)
    above = jnp.clip(below + 1, 0, num_buckets - 1)
    x0 = jnp.squeeze(x, -1)
    # below==above at the saturated top bucket: both distances are 0 there,
    # so force them to 1 (reference's `equal` branch) → 0.5+0.5 on one bucket
    equal = below == above
    dist_below = jnp.where(equal, 1.0, jnp.abs(buckets[below] - x0))
    dist_above = jnp.where(equal, 1.0, jnp.abs(buckets[above] - x0))
    total = dist_below + dist_above
    w_below = dist_above / total
    w_above = dist_below / total
    enc = (
        jax.nn.one_hot(below, num_buckets, dtype=x.dtype) * w_below[..., None]
        + jax.nn.one_hot(above, num_buckets, dtype=x.dtype) * w_above[..., None]
    )
    return enc


def two_hot_decoder(probs: jax.Array, support_range: int = 300) -> jax.Array:
    """Inverse of :func:`two_hot_encoder`: expectation over bucket centers,
    then symexp.  (..., num_buckets) → (..., 1)."""
    num_buckets = probs.shape[-1]
    buckets = jnp.linspace(-support_range, support_range, num_buckets, dtype=probs.dtype)
    return symexp(jnp.sum(probs * buckets, axis=-1, keepdims=True))


# --------------------------------------------------------------------------
# misc numerics
# --------------------------------------------------------------------------

def normalize_tensor(x: jax.Array, eps: float = 1e-8, mask: Optional[jax.Array] = None) -> jax.Array:
    # ddof=1: torch.std is unbiased (reference: sheeprl/utils/utils.py:126)
    if mask is not None:  # mean and deviation over the steps that count (mask 1) alone
        n = jnp.maximum(mask.sum(), 1.0)
        mean = (x * mask).sum() / n
        std = jnp.sqrt((mask * (x - mean) ** 2).sum() / jnp.maximum(n - 1.0, 1.0))
        return (x - mean) / (std + eps)
    return (x - x.mean()) / (x.std(ddof=1) + eps)


def polynomial_decay(
    current_step: int,
    *,
    initial: float = 1.0,
    final: float = 0.0,
    max_decay_steps: int = 100,
    power: float = 1.0,
) -> float:
    """Host-side polynomial schedule (reference: sheeprl/utils/utils.py:133-144)."""
    if current_step > max_decay_steps or initial == final:
        return final
    frac = (1 - current_step / max_decay_steps) ** power
    return (initial - final) * frac + final


def safetanh(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    return jnp.clip(jnp.tanh(x), -1.0 + eps, 1.0 - eps)


def safeatanh(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    return jnp.arctanh(jnp.clip(x, -1.0 + eps, 1.0 - eps))


def window_scan(body, carry, xs, unroll_limit: int = 16, unroll: bool = True):
    """``lax.scan`` over an update window, UNROLLED as a traced Python loop
    on the CPU backend for small convolution-bearing windows.

    Measured on XLA-CPU: a convolution-bearing
    update body runs ~5x slower inside ``lax.scan``'s outlined call (19.4 s
    vs 3.5 s for the identical DreamerV1 benchmark-sized update; the
    penalty is per iteration and ``lax.scan(..., unroll=True)`` does not
    remove it — only true trace-time inlining does).  Pure-matmul bodies
    show no such penalty, and unrolling them only inflates compile time
    (the PPO CartPole benchmark DOUBLED from the bigger program), so
    callers pass ``unroll=False`` for conv-free bodies.  On TPU the
    outlined while-loop is the right lowering (compile time stays flat),
    so scan is always kept there.

    Compile cadence is unchanged either way: the window length already
    participates in the input shape signature, so each distinct ``U``
    compiled before and still does.
    """
    leaves = jax.tree.leaves(xs)
    length = int(leaves[0].shape[0]) if leaves else 0
    if any(l.shape[0] != length for l in leaves):  # keep lax.scan's guarantee
        raise ValueError(
            f"window_scan: inconsistent leading dims {[l.shape[0] for l in leaves]}"
        )
    backend = jax.default_backend()
    if not unroll or backend != "cpu" or length == 0 or length > unroll_limit:
        return jax.lax.scan(body, carry, xs)
    ys = []
    for u in range(length):
        x_u = jax.tree.map(lambda v: v[u], xs)
        carry, y = body(carry, x_u)
        ys.append(y)
    stacked = jax.tree.map(lambda *vs: jnp.stack(vs, 0), *ys)
    return carry, stacked


def merge_framestack(x, xp=np):
    """``(..., S, H, W, C)`` framestacked pixels -> ``(..., H, W, S*C)``.

    One source of truth for the stack-to-channels layout every pixel path
    uses — train blocks, player/rollout prep, device-mirror gathers
    (``xp=jnp`` runs the permute on device).  Arbitrary leading batch dims.
    """
    s = x.shape
    x = xp.moveaxis(x, -4, -2)  # (..., H, W, S, C)
    return x.reshape(*s[:-4], s[-3], s[-2], s[-4] * s[-1])


def probe_bytes_per_update(rb, batch_size: int, **sample_kwargs) -> float:
    """Host-side byte cost of ONE update's sampled batch (for window_chunks).

    Draws a 1-update probe sample and sums leaf nbytes; snapshots/restores
    the global numpy RNG so the probe does not shift the sampling stream
    (goldens pin it).
    """
    rng_state = np.random.get_state()
    try:
        probe = rb.sample(batch_size, n_samples=1, **sample_kwargs)
    finally:
        np.random.set_state(rng_state)
    return float(sum(np.asarray(v).nbytes for v in probe.values()))


def mirror_hbm_bytes_per_update(
    obs_space: Any, cnn_keys, batch_size: int, rows: int = 1
) -> float:
    """Per-update HBM bytes of the device-GATHERED pixel block when the
    replay mirror is on (the pixels never ship H2D; the ring is uint8, so
    1 byte/px).  ``rows`` is how many gathered pixel rows each sampled
    transition contributes: the sequence length for sequential samplers
    (Dreamer), 2 for transition samplers that gather obs + next_obs
    (SAC-AE).  Feed the result to ``window_chunks(hbm_bytes_per_update=...)``
    so both loops budget the same formula."""
    return float(
        sum(int(np.prod(obs_space[k].shape)) for k in cnn_keys)
        * int(rows)
        * int(batch_size)
    )


def window_chunks(
    n_updates: int,
    bytes_per_update: float,
    budget_bytes: Optional[float] = None,
    hbm_bytes_per_update: float = 0.0,
):
    """DEPRECATED: the algo loops now chunk purely for compile reuse via
    ``data/device_replay.update_chunks`` — with the replay ring
    device-resident (``buffer.device``) there is no shipped H2D block to
    byte-budget.  Kept (with ``probe_bytes_per_update`` /
    ``mirror_hbm_bytes_per_update``) for external callers on the host path.

    Original contract: split an update window into dispatch chunk sizes
    whose shipped ``(U, ...)`` batch block stays under a device byte budget.

    The first window after ``learning_starts`` is a burst: the ratio
    governor repays every pre-training env step at once, so e.g.
    ``learning_starts=1024`` at replay_ratio 1 demands U=1024 — sampled and
    shipped as ONE uint8 block that is 12.9 GiB raw / 25.8 GiB in padded
    TPU layout for (1024, 64, 16, 64, 64, 3), over a 16 GiB HBM chip
    (the round-5 TPU learning capture died on exactly that alloc).
    Chunking caps per-dispatch block bytes; steady-state windows are far
    below the budget and stay single-dispatch.  Budget default 1 GiB
    (override ``SHEEPRL_MAX_WINDOW_BYTES``) — the padded-layout worst case
    observed is 2x raw, leaving ample HBM for params/activations.

    Chunk sizes are powers of two (largest fitting the budget, greedily
    decomposing the remainder) — every distinct chunk length compiles its
    own train-phase executable, and a remote TPU compile costs minutes, so
    a burst must reuse a handful of shapes rather than mint arbitrary ones
    (and the small tail chunks coincide with the steady-state window sizes,
    which are also tiny powers of two).

    ``bytes_per_update`` is the SHIPPED (H2D) cost of one update's batch.
    With the device mirror, pixel sequences never ship — but the on-device
    gathered ``(U, ...)`` pixel block still consumes HBM; pass its per-update
    bytes as ``hbm_bytes_per_update`` so the chunk cap honors BOTH ceilings
    (``SHEEPRL_MAX_HBM_WINDOW_BYTES``, default 2 GiB — the gathered block
    lives on-chip only, no padded-H2D-layout 2x, so it gets a looser cap
    than the shipped budget).
    """
    if budget_bytes is None:
        budget_bytes = float(os.environ.get("SHEEPRL_MAX_WINDOW_BYTES", 2**30))
    max_u = max(1, int(budget_bytes // max(bytes_per_update, 1.0)))
    if hbm_bytes_per_update > 0.0:
        hbm_budget = float(os.environ.get("SHEEPRL_MAX_HBM_WINDOW_BYTES", 2**31))
        max_u = min(max_u, max(1, int(hbm_budget // hbm_bytes_per_update)))
    cap = 1 << (max_u.bit_length() - 1)  # largest power of two <= max_u
    chunks = []
    remaining = int(n_updates)
    while remaining > 0:
        step = min(cap, 1 << (remaining.bit_length() - 1))
        chunks.append(step)
        remaining -= step
    return chunks


def should_unroll_updates(cnn_keys, n_bodies: int, limit: int = 32) -> bool:
    """One source of truth for the PPO-family two-level unroll decision:
    conv trunk present (the penalty is conv-specific), CPU backend, and a
    total body count small enough to compile unrolled."""
    return bool(cnn_keys) and jax.default_backend() == "cpu" and n_bodies <= limit


# --------------------------------------------------------------------------
# replay-ratio governor
# --------------------------------------------------------------------------

class TrainWindow:
    """Accrue ``Ratio``-owed gradient steps over K env iterations and release
    them as ONE scanned dispatch (``algo.train_window_iters``).

    Update math and count are exactly preserved — only the dispatch cadence
    changes (data staleness within a window is at most K-1 iterations, the
    reference's decoupled-trainer staleness class).  Shared by the SAC and
    SAC-AE loops so the flush rule cannot drift between them.
    """

    def __init__(self, window_iters: int, pending: int = 0):
        self.window_iters = max(int(window_iters), 1)
        self.pending = int(pending)

    def push(self, granted: int, update: int, learning_starts: int, total_iters: int) -> int:
        """Add this iteration's granted steps; return the number to run NOW
        (0 while the window is filling).  The last iteration always flushes
        so no owed step is ever dropped."""
        self.pending += int(granted)
        window_full = (update - learning_starts) % self.window_iters == self.window_iters - 1
        if self.pending > 0 and (window_full or update == total_iters):
            out, self.pending = self.pending, 0
            return out
        return 0


class Ratio:
    """Keeps gradient-steps : env-steps at a configured ratio.

    Host-side control flow by design: the number of updates per iteration is
    data-dependent, which must stay outside jit (SURVEY.md §7 hard part 2).
    Mirrors the accounting of the reference governor
    (reference: sheeprl/utils/utils.py:259-300).
    """

    def __init__(self, ratio: float, pretrain_steps: int = 0):
        if pretrain_steps < 0:
            raise ValueError(f"pretrain_steps must be non-negative, got {pretrain_steps}")
        if ratio < 0:
            raise ValueError(f"ratio must be non-negative, got {ratio}")
        self._ratio = float(ratio)
        self._pretrain_steps = int(pretrain_steps)
        self._prev: Optional[float] = None

    def __call__(self, in_steps: int) -> int:
        # Hafner's law, matching the reference exactly
        # (reference: sheeprl/utils/utils.py:273-291): the FIRST call converts
        # pretrain_steps (clamped to the current step count, in STEP units)
        # when set, else the current steps; later calls convert the delta and
        # carry the fractional remainder in step units via ``_prev``.
        if self._ratio == 0:
            return 0
        if self._prev is None:
            self._prev = in_steps
            if self._pretrain_steps > 0:
                if in_steps < self._pretrain_steps:
                    warnings.warn(
                        "pretrain_steps exceeds the current step count; clamping "
                        "to the current steps (reference behavior)", UserWarning
                    )
                    self._pretrain_steps = in_steps
                return int(self._pretrain_steps * self._ratio)
            return int(in_steps * self._ratio)
        repeats = int((in_steps - self._prev) * self._ratio)
        self._prev += repeats / self._ratio
        return repeats

    def state_dict(self) -> Dict[str, Any]:
        return {
            "ratio": self._ratio,
            "pretrain_steps": self._pretrain_steps,
            "prev": self._prev,
        }

    def load_state_dict(self, state: Dict[str, Any]) -> "Ratio":
        self._ratio = float(state["ratio"])
        self._pretrain_steps = int(state["pretrain_steps"])
        if "prev" in state:
            self._prev = None if state["prev"] is None else float(state["prev"])
        else:
            # legacy layout (accumulator-based): translate so a resumed run
            # keeps the same future output stream
            prev_in = int(state["prev_in_steps"])
            accum = float(state["accum"])
            if prev_in == 0 and accum == 0.0:
                self._prev = None
            else:
                self._prev = prev_in - (accum / self._ratio if self._ratio else 0.0)
        return self


# --------------------------------------------------------------------------
# config persistence / misc host helpers
# --------------------------------------------------------------------------

def save_configs(cfg: Any, log_dir: str) -> None:
    os.makedirs(log_dir, exist_ok=True)
    as_dict = cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg)
    with open(os.path.join(log_dir, "config.yaml"), "w") as f:
        yaml.safe_dump(as_dict, f, sort_keys=False)


def print_config(cfg: Any) -> None:
    try:
        from rich.pretty import pprint

        pprint(cfg.as_dict() if hasattr(cfg, "as_dict") else cfg, expand_all=False)
    except Exception:
        print(yaml.safe_dump(cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg)))


def unwrap_fabric(module: Any) -> Any:  # parity shim; no wrapping in JAX
    return module


def dict_to_numpy(tree: Dict[str, Any]) -> Dict[str, np.ndarray]:
    return {k: np.asarray(v) for k, v in tree.items()}


def copy_cfg(cfg: Any) -> Any:
    return copy.deepcopy(cfg)


def device_sync(tree: Any = None) -> None:
    """Device fence: block the host until device work has FINISHED.

    ``block_until_ready`` over every ``jax.Array`` leaf of ``tree`` (or over
    every live array when ``tree`` is None).  Deleted (donated) buffers are
    skipped.  ``chip_smoke.py`` checks on the chip that the fence is honest:
    the fenced wall time of a chain of dependent matmuls scales with the
    chain's length.
    """
    leaves = jax.live_arrays() if tree is None else jax.tree_util.tree_leaves(tree)
    for a in leaves:
        if isinstance(a, jax.Array) and not a.is_deleted():
            a.block_until_ready()


def force_cpu_backend() -> bool:
    """Pin this process's default JAX backend to CPU.  Returns False (with a
    visible warning) if backends were already initialized — in that case the
    caller's subsequent device use may still target the accelerator."""
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
        return True
    except Exception as e:  # pragma: no cover - depends on init order
        print(f"[sheeprl_tpu] WARNING: could not force CPU backend: {e}", flush=True)
        return False
