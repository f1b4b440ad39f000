"""Pallas TPU kernel: fused LayerNorm-GRU cell.

The LayerNorm-GRU cell is the hot recurrent op of every Dreamer
(SURVEY.md §7: "Pallas fused LayerNorm-GRU cell is the stretch goal").  The
cell is one fused matmul followed by LayerNorm and three gate nonlinearities
(see sheeprl_tpu/models/models.py:LayerNormGRUCell); XLA already fuses the
elementwise tail, but routes the (B, 3H) projection through HBM between the
matmul and the normalization.  This kernel keeps the projection resident in
VMEM: concat → MXU matmul → fp32 LayerNorm → gates → new state, one pass.

Layout: grid over batch tiles; the full (D+H, 3H) weight block stays in VMEM
for every grid step (fits for Dreamer S/M sizes: e.g. S → (1536, 1536) fp32
= 9.4 MB < 16 MB VMEM).  For XL-scale recurrent states shard H over the
mesh instead (LN is per-3H-row; the gate split is H-blocked, so a model-axis
sharding composes).

Use via ``fused_layernorm_gru(...)`` — numerically identical (fp32) to the
flax cell; validated against it in tests/test_models/test_gru_pallas.py with
``interpret=True`` (no TPU needed).  Enable inside models with
``LayerNormGRUCell(use_pallas=True)``.

HARDWARE STATUS (v5e, scan-based timing — 2026-07-31 capture, deleted in PR 23, see git history;
not measured on the current code):
Mosaic-compiles and matches the flax cell to <3e-6, but LOSES to XLA's
fused scan body at every shape (speedup 0.38-0.56x; H=512/B=16: 11.3 µs vs
XLA 4.5 µs per step) — XLA already keeps the scan working set VMEM-resident.
RULING: XLA path stays the default; the kernel remains as a
correctness-validated reference implementation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LN_EPS = 1e-5  # matches models.LayerNorm default


def _gru_kernel(x_ref, h_ref, w_ref, scale_ref, bias_ref, out_ref):
    """One batch-tile of the fused cell.

    x: (Bt, D) input features;  h: (Bt, H) carried state;
    w: (D+H, 3H) fused projection;  scale/bias: (1, 3H) LayerNorm params.
    """
    x = x_ref[:]
    h = h_ref[:]
    w = w_ref[:]
    inp = jnp.concatenate([x, h], axis=-1)
    # MXU: (Bt, D+H) @ (D+H, 3H), fp32 accumulation
    parts = jnp.dot(inp, w, preferred_element_type=jnp.float32)
    # fp32 LayerNorm over the 3H axis (matches models.LayerNorm eps)
    mean = jnp.mean(parts, axis=-1, keepdims=True)
    var = jnp.mean((parts - mean) ** 2, axis=-1, keepdims=True)
    parts = (parts - mean) * jax.lax.rsqrt(var + LN_EPS)
    parts = parts * scale_ref[:] + bias_ref[:]
    # gate split / nonlinearities (Hafner variant: update bias -1)
    H = h.shape[-1]
    reset = jax.nn.sigmoid(parts[:, :H])
    cand = jnp.tanh(reset * parts[:, H:2 * H])
    update = jax.nn.sigmoid(parts[:, 2 * H:] - 1.0)
    out_ref[:] = update * cand + (1.0 - update) * h


def fused_layernorm_gru(
    x: jax.Array,
    h: jax.Array,
    w: jax.Array,
    ln_scale: jax.Array,
    ln_bias: jax.Array,
    block_b: int = 128,
    interpret: bool = None,
) -> jax.Array:
    if interpret is None:
        # only TPU has the Mosaic backend: fall back to the interpreter
        # everywhere else (CPU tests, GPU dev boxes)
        interpret = jax.default_backend() != "tpu"
    # accept arbitrary leading batch dims like the flax cell
    lead = x.shape[:-1]
    if len(lead) > 1:
        x = x.reshape(-1, x.shape[-1])
        h = h.reshape(-1, h.shape[-1])
        out = _fused_layernorm_gru(x, h, w, ln_scale, ln_bias, block_b, interpret)
        return out.reshape(*lead, out.shape[-1])
    return _fused_layernorm_gru(x, h, w, ln_scale, ln_bias, block_b, interpret)


def _reference_math(x, h, w, ln_scale, ln_bias):
    """Pure-JAX same-math path (fp32): autodiff source for the backward."""
    f32 = jnp.float32
    h = h.astype(f32)
    inp = jnp.concatenate([x.astype(f32), h], axis=-1)
    parts = jnp.dot(inp, w.astype(f32))
    mean = jnp.mean(parts, axis=-1, keepdims=True)
    var = jnp.mean((parts - mean) ** 2, axis=-1, keepdims=True)
    parts = (parts - mean) * jax.lax.rsqrt(var + LN_EPS)
    parts = parts * ln_scale.astype(f32).reshape(1, -1) + ln_bias.astype(f32).reshape(1, -1)
    H = h.shape[-1]
    reset = jax.nn.sigmoid(parts[:, :H])
    cand = jnp.tanh(reset * parts[:, H:2 * H])
    update = jax.nn.sigmoid(parts[:, 2 * H:] - 1.0)
    return update * cand + (1.0 - update) * h


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _gru_core(x, h, w, ln_scale, ln_bias, block_b, interpret):
    return _pallas_forward(x, h, w, ln_scale, ln_bias, block_b, interpret)


def _gru_core_fwd(x, h, w, ln_scale, ln_bias, block_b, interpret):
    out = _pallas_forward(x, h, w, ln_scale, ln_bias, block_b, interpret)
    return out, (x, h, w, ln_scale, ln_bias)


def _gru_core_bwd(block_b, interpret, residuals, g):
    # pallas_call has no reverse-mode rule; differentiate the same math via
    # XLA (what the flax path's backward is anyway)
    _, vjp = jax.vjp(_reference_math, *residuals)
    return vjp(g)


_gru_core.defvjp(_gru_core_fwd, _gru_core_bwd)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def _fused_layernorm_gru(
    x: jax.Array,
    h: jax.Array,
    w: jax.Array,
    ln_scale: jax.Array,
    ln_bias: jax.Array,
    block_b: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Fused LayerNorm-GRU step.

    Args:
        x: (B, D) inputs. h: (B, H) previous state. w: (D+H, 3H) fused
        kernel (the flax cell's ``fused`` Dense, bias-free). ln_scale/ln_bias:
        (3H,) LayerNorm parameters.
    Returns:
        (B, H) new recurrent state (fp32).
    """
    return _gru_core(x, h, w, ln_scale, ln_bias, block_b, interpret)


# conservative VMEM budget for the resident weight block (see rssm_pallas)
_VMEM_WEIGHT_BUDGET_BYTES = 12 * 1024 * 1024


def _pallas_forward(
    x: jax.Array,
    h: jax.Array,
    w: jax.Array,
    ln_scale: jax.Array,
    ln_bias: jax.Array,
    block_b: int = 128,
    interpret: bool = False,
) -> jax.Array:
    if 4 * w.size > _VMEM_WEIGHT_BUDGET_BYTES:
        raise ValueError(
            f"fused GRU kernel keeps the (D+H, 3H) weight VMEM-resident; "
            f"{4 * w.size / 2**20:.1f} MB fp32 exceeds the "
            f"{_VMEM_WEIGHT_BUDGET_BYTES / 2**20:.0f} MB budget — use the "
            "flax cell (use_pallas=False) or shard H over the mesh."
        )
    B, D = x.shape
    H = h.shape[-1]
    x = x.astype(jnp.float32)
    h = h.astype(jnp.float32)
    w = w.astype(jnp.float32)
    scale = ln_scale.reshape(1, 3 * H).astype(jnp.float32)
    bias = ln_bias.reshape(1, 3 * H).astype(jnp.float32)

    bt = min(block_b, B)
    # pad B to a multiple of the tile
    pad = (-B) % bt
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        h = jnp.pad(h, ((0, pad), (0, 0)))
    grid = ((B + pad) // bt,)

    out = pl.pallas_call(
        _gru_kernel,
        out_shape=jax.ShapeDtypeStruct((B + pad, H), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, D), lambda i: (i, 0)),
            pl.BlockSpec((bt, H), lambda i: (i, 0)),
            pl.BlockSpec((D + H, 3 * H), lambda i: (0, 0)),
            pl.BlockSpec((1, 3 * H), lambda i: (0, 0)),
            pl.BlockSpec((1, 3 * H), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bt, H), lambda i: (i, 0)),
        interpret=interpret,
    )(x, h, w, scale, bias)
    return out[:B]
