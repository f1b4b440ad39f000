"""Pallas TPU kernel: fully-fused RSSM recurrent path.

The RSSM recurrent step (reference: sheeprl/algos/dreamer_v3/agent.py:281-341
``RecurrentModel``) is ``dense+LN+SiLU`` over ``z ⊕ a`` followed by the
LayerNorm-GRU cell — two matmuls with elementwise tails, executed once per
sequence step inside a ``lax.scan``.  XLA fuses each tail into its matmul but
still stages the intermediate ``(B, D)`` activation and the ``(B, 3H)`` gate
projection through HBM every step.  This kernel runs the WHOLE path in one
``pallas_call``: both weight blocks stay resident in VMEM for every batch
tile, the intermediates never leave VMEM, and the new recurrent state is the
only output.

Sizes (DreamerV3-S, fp32): W_in (1056, 512) ≈ 2.2 MB, W_gru (1024, 1536)
≈ 6.3 MB → comfortably inside the ~16 MB VMEM budget, so the S/XS kernel
keeps both weight blocks fully VMEM-resident.  M and larger presets exceed
VMEM with fp32 weights (L: W_gru (2816, 6144) ≈ 69 MB) — those dispatch to
the H-TILED kernel below (``_pallas_forward_tiled``): the gate projection
``w_gru`` streams through VMEM in column tiles over a second grid axis
while the raw gate pre-activations accumulate into a VMEM scratch; at the
last column step the full-row (3H) LayerNorm — which couples ALL gate
columns and is why a naive column tiling is wrong — plus the gate
nonlinearities and the state update run from scratch, and only the (B, H)
new state is written to HBM.  The intermediate (B, 3H) block never touches
HBM at ANY preset size.

Autodiff: ``pallas_call`` has no reverse-mode rule, so the op carries a
``custom_vjp`` whose backward differentiates the SAME math via XLA.  The
backward re-runs the forward (rematerialization semantics) — in gradient
paths the fused kernel therefore trades a little recompute for the VMEM
residency; the clear wins are the grad-free player/rollout and posterior
paths, and any training setup already under ``jax.checkpoint``.  Decide
per-preset with benchmarks/bench_gru_pallas.py on hardware.

Numerics match the flax path exactly (fp32 throughout): input LN eps 1e-3,
GRU LN eps 1e-5 (models.LayerNorm defaults), Hafner ``-1`` update-gate bias.
Validated against the flax modules in tests/test_models/test_rssm_pallas.py
with ``interpret=True`` (no TPU needed).  Enable inside the world model with
``algo.world_model.recurrent_model.fused_pallas=True`` once on TPU hardware.

HARDWARE STATUS (v5e, scan-based timing — 2026-07-31 capture, deleted in PR 23, see git history;
not measured on the current code):
Mosaic-compiles and matches the XLA path to <1e-4 at every preset shape,
but LOSES to XLA's fused scan body on all of them (speedup 0.18-0.47x;
e.g. D=512/H=512/B=16: 13.2 µs vs XLA 4.4 µs per step).  XLA already keeps
this working set in VMEM across scan iterations; the kernel's VMEM-residency
premise buys nothing and its fp32 MXU path gives up bf16.  RULING:
the XLA path stays the default; these kernels remain as correctness-validated
reference implementations (`fused_pallas=True` still dispatches them).  The VMEM planner (`_plan_tiled`) sizes the tiled variant's
working set against `_VMEM_WEIGHT_BUDGET_BYTES` and raises when no legal
tiling fits, instead of letting Mosaic fail opaquely.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LN_IN_EPS = 1e-3   # RecurrentModel input LayerNorm (agent.py RecurrentModel)
LN_GRU_EPS = 1e-5  # models.LayerNorm default (GRU projection LN)


def _ln(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float) -> jax.Array:
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _rssm_kernel(
    x_ref, h_ref,
    w_in_ref, b_in_ref, ln_in_scale_ref, ln_in_bias_ref,
    w_gru_ref, gru_scale_ref, gru_bias_ref,
    out_ref,
):
    """One batch tile of the fused recurrent path.

    x: (Bt, Z+A) concatenated stochastic state + action;  h: (Bt, H);
    w_in/b_in: (Z+A, D)/(1, D) input projection;  ln_in_*: (1, D);
    w_gru: (D+H, 3H) fused GRU projection;  gru_*: (1, 3H) GRU LayerNorm.
    """
    x = x_ref[:]
    h = h_ref[:]
    # input projection + LN(1e-3) + SiLU — all VMEM-resident
    y = jnp.dot(x, w_in_ref[:], preferred_element_type=jnp.float32) + b_in_ref[:]
    y = _ln(y, ln_in_scale_ref[:], ln_in_bias_ref[:], LN_IN_EPS)
    y = jax.nn.silu(y)
    # LayerNorm-GRU (same math as ops/gru_pallas._gru_kernel)
    inp = jnp.concatenate([y, h], axis=-1)
    parts = jnp.dot(inp, w_gru_ref[:], preferred_element_type=jnp.float32)
    parts = _ln(parts, gru_scale_ref[:], gru_bias_ref[:], LN_GRU_EPS)
    H = h.shape[-1]
    reset = jax.nn.sigmoid(parts[:, :H])
    cand = jnp.tanh(reset * parts[:, H:2 * H])
    update = jax.nn.sigmoid(parts[:, 2 * H:] - 1.0)
    out_ref[:] = update * cand + (1.0 - update) * h


def fused_rssm_recurrent(
    x: jax.Array,
    h: jax.Array,
    w_in: jax.Array,
    b_in: jax.Array,
    ln_in_scale: jax.Array,
    ln_in_bias: jax.Array,
    w_gru: jax.Array,
    gru_scale: jax.Array,
    gru_bias: jax.Array,
    block_b: int = 128,
    interpret: bool = None,
) -> jax.Array:
    """Fused ``RecurrentModel`` forward: ``GRU(h, SiLU(LN(x @ W_in + b)))``.

    Args:
        x: (..., Z+A) inputs (z ⊕ action).  h: (..., H) recurrent state.
        w_in/b_in: input Dense params.  ln_in_*: input LayerNorm params (D,).
        w_gru: (D+H, 3H) fused GRU kernel.  gru_*: GRU LayerNorm params (3H,).
    Returns:
        (..., H) new recurrent state, fp32.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    lead = x.shape[:-1]
    if len(lead) > 1:
        x = x.reshape(-1, x.shape[-1])
        h = h.reshape(-1, h.shape[-1])
        out = _fused_rssm_recurrent(
            x, h, w_in, b_in, ln_in_scale, ln_in_bias, w_gru, gru_scale, gru_bias,
            block_b, interpret,
        )
        return out.reshape(*lead, out.shape[-1])
    return _fused_rssm_recurrent(
        x, h, w_in, b_in, ln_in_scale, ln_in_bias, w_gru, gru_scale, gru_bias,
        block_b, interpret,
    )


def _reference_math(x, h, w_in, b_in, ln_in_scale, ln_in_bias, w_gru, gru_scale, gru_bias):
    """Pure-JAX implementation of the same math (fp32) — the autodiff source
    for the kernel's backward pass and the numerical reference in tests."""
    f32 = jnp.float32
    y = jnp.dot(x.astype(f32), w_in.astype(f32)) + b_in.astype(f32).reshape(1, -1)
    y = _ln(y, ln_in_scale.astype(f32).reshape(1, -1), ln_in_bias.astype(f32).reshape(1, -1), LN_IN_EPS)
    y = jax.nn.silu(y)
    h = h.astype(f32)
    inp = jnp.concatenate([y, h], axis=-1)
    parts = jnp.dot(inp, w_gru.astype(f32))
    parts = _ln(parts, gru_scale.astype(f32).reshape(1, -1), gru_bias.astype(f32).reshape(1, -1), LN_GRU_EPS)
    H = h.shape[-1]
    reset = jax.nn.sigmoid(parts[:, :H])
    cand = jnp.tanh(reset * parts[:, H:2 * H])
    update = jax.nn.sigmoid(parts[:, 2 * H:] - 1.0)
    return update * cand + (1.0 - update) * h


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10))
def _rssm_core(x, h, w_in, b_in, ln_in_scale, ln_in_bias, w_gru, gru_scale, gru_bias,
               block_b, interpret):
    return _pallas_forward(
        x, h, w_in, b_in, ln_in_scale, ln_in_bias, w_gru, gru_scale, gru_bias,
        block_b, interpret,
    )


def _rssm_core_fwd(x, h, w_in, b_in, ln_in_scale, ln_in_bias, w_gru, gru_scale, gru_bias,
                   block_b, interpret):
    out = _pallas_forward(
        x, h, w_in, b_in, ln_in_scale, ln_in_bias, w_gru, gru_scale, gru_bias,
        block_b, interpret,
    )
    return out, (x, h, w_in, b_in, ln_in_scale, ln_in_bias, w_gru, gru_scale, gru_bias)


def _rssm_core_bwd(block_b, interpret, residuals, g):
    # backward through the SAME math via XLA autodiff — pallas_call has no
    # reverse-mode rule; XLA's fused backward is what the flax path uses too
    _, vjp = jax.vjp(_reference_math, *residuals)
    return vjp(g)


_rssm_core.defvjp(_rssm_core_fwd, _rssm_core_bwd)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def _fused_rssm_recurrent(
    x, h, w_in, b_in, ln_in_scale, ln_in_bias, w_gru, gru_scale, gru_bias,
    block_b: int = 128,
    interpret: bool = False,
):
    return _rssm_core(
        x, h, w_in, b_in, ln_in_scale, ln_in_bias, w_gru, gru_scale, gru_bias,
        block_b, interpret,
    )


# conservative VMEM budget for the weight blocks (v5e has 16 MB/core; leave
# headroom for activations and double-buffering)
_VMEM_WEIGHT_BUDGET_BYTES = 12 * 1024 * 1024


def _pallas_forward(
    x, h, w_in, b_in, ln_in_scale, ln_in_bias, w_gru, gru_scale, gru_bias,
    block_b: int = 128,
    interpret: bool = False,
):
    weight_bytes = 4 * (w_in.size + w_gru.size)
    if weight_bytes > _VMEM_WEIGHT_BUDGET_BYTES:
        # M/L/XL presets: stream w_gru in column tiles instead (same math,
        # same single-HBM-write-per-row-block contract)
        return _pallas_forward_tiled(
            x, h, w_in, b_in, ln_in_scale, ln_in_bias, w_gru, gru_scale, gru_bias,
            block_b=min(block_b, 64), interpret=interpret,
        )
    B, ZA = x.shape
    H = h.shape[-1]
    D = w_in.shape[-1]
    f32 = jnp.float32
    x = x.astype(f32)
    h = h.astype(f32)
    w_in = w_in.astype(f32)
    b_in = b_in.reshape(1, D).astype(f32)
    ln_in_scale = ln_in_scale.reshape(1, D).astype(f32)
    ln_in_bias = ln_in_bias.reshape(1, D).astype(f32)
    w_gru = w_gru.astype(f32)
    gru_scale = gru_scale.reshape(1, 3 * H).astype(f32)
    gru_bias = gru_bias.reshape(1, 3 * H).astype(f32)

    bt = min(block_b, B)
    pad = (-B) % bt
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        h = jnp.pad(h, ((0, pad), (0, 0)))
    grid = ((B + pad) // bt,)

    out = pl.pallas_call(
        _rssm_kernel,
        out_shape=jax.ShapeDtypeStruct((B + pad, H), f32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, ZA), lambda i: (i, 0)),
            pl.BlockSpec((bt, H), lambda i: (i, 0)),
            pl.BlockSpec((ZA, D), lambda i: (0, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((D + H, 3 * H), lambda i: (0, 0)),
            pl.BlockSpec((1, 3 * H), lambda i: (0, 0)),
            pl.BlockSpec((1, 3 * H), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bt, H), lambda i: (i, 0)),
        interpret=interpret,
    )(x, h, w_in, b_in, ln_in_scale, ln_in_bias, w_gru, gru_scale, gru_bias)
    return out[:B]


# ---------------------------------------------------------------------------
# H-tiled variant for M/L/XL presets (w_gru too large for VMEM residency)
# ---------------------------------------------------------------------------

def _rssm_kernel_tiled(
    x_ref, h_ref,
    w_in_ref, b_in_ref, ln_in_scale_ref, ln_in_bias_ref,
    w_gru_ref, gru_scale_ref, gru_bias_ref,
    out_ref,
    y_scratch, parts_scratch,
):
    """One (batch tile, gate-column tile) step of the streamed recurrent path.

    Grid is (num_batch_tiles, num_col_tiles); for a fixed batch tile the
    column axis runs sequentially, streaming ``w_gru`` (D+H, tj) tiles from
    HBM.  ``y`` (the input projection) is computed once at j==0 into VMEM
    scratch; every j accumulates its raw gate pre-activation columns into
    ``parts_scratch``; the last j applies the full-3H LayerNorm (it couples
    every gate column — the reason this kernel is two-phase) + gates + state
    update and performs the kernel's only HBM write.
    """
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    D = y_scratch.shape[-1]
    H = h_ref.shape[-1]
    tj = w_gru_ref.shape[-1]

    @pl.when(j == 0)
    def _input_projection():
        y = jnp.dot(x_ref[:], w_in_ref[:], preferred_element_type=jnp.float32) + b_in_ref[:]
        y = _ln(y, ln_in_scale_ref[:], ln_in_bias_ref[:], LN_IN_EPS)
        y_scratch[:] = jax.nn.silu(y)

    # this column tile's raw pre-activations: [y, h] @ w_gru[:, jt]
    parts_tile = (
        jnp.dot(y_scratch[:], w_gru_ref[:D, :], preferred_element_type=jnp.float32)
        + jnp.dot(h_ref[:], w_gru_ref[D:, :], preferred_element_type=jnp.float32)
    )
    parts_scratch[:, pl.ds(j * tj, tj)] = parts_tile

    @pl.when(j == nj - 1)
    def _finalize():
        parts = _ln(parts_scratch[:], gru_scale_ref[:], gru_bias_ref[:], LN_GRU_EPS)
        h = h_ref[:]
        reset = jax.nn.sigmoid(parts[:, :H])
        cand = jnp.tanh(reset * parts[:, H:2 * H])
        update = jax.nn.sigmoid(parts[:, 2 * H:] - 1.0)
        out_ref[:] = update * cand + (1.0 - update) * h


def _col_tile(total: int, target: int = 512) -> int:
    """Largest divisor of ``total`` that is ≤ target and a multiple of 128
    (TPU lane width); falls back to ``total`` for small models."""
    if total <= target:
        return total
    for t in range(target, 127, -128):
        if total % t == 0:
            return t
    return total


def _tiled_vmem_bytes(bt: int, tj: int, ZA: int, D: int, H: int) -> int:
    """Estimated VMEM residency of one `_rssm_kernel_tiled` step (fp32):
    resident w_in block, the streamed w_gru column tile (×2 for pallas
    double-buffering), both scratches, and the batch-tile operands/output."""
    return 4 * (
        ZA * D                # w_in (resident across the column axis)
        + 2 * (D + H) * tj    # streamed w_gru tile, double-buffered
        + bt * D              # y scratch
        + bt * 3 * H          # parts scratch
        + bt * (ZA + 2 * H)   # x, h, out tiles
        + 3 * D + 2 * 3 * H   # LN/bias vectors
    )


def _legal_col_tiles(total: int, target: int = 512) -> list:
    """Legal column tiles for a ``total``-wide axis, descending: every
    divisor of ``total`` that is a multiple of 128 and ≤ target, seeded with
    the :func:`_col_tile` choice.  When no 128-multiple divides ``total``
    (3H < 128 or an odd width) the only legal tile is ``total`` itself
    (ADVICE r4: stepping down from tj in raw -128 increments could miss
    every divisor and give up while a smaller legal tile existed)."""
    tiles = {t for t in range(128, min(total, target) + 1, 128) if total % t == 0}
    tiles.add(_col_tile(total, target))
    return sorted(tiles, reverse=True)


def _plan_tiled(B: int, ZA: int, D: int, H: int, block_b: int):
    """Pick (bt, tj) so the tiled kernel's working set fits the VMEM budget
    (ADVICE r3: the tiled path previously had no accounting at all and XL
    could exceed ~16MB/core).  Prefers shrinking the column tile first (it
    only adds grid steps), then the batch tile; raises when even the
    smallest legal tiling cannot fit."""
    bt = min(block_b, B)
    col_tiles = _legal_col_tiles(3 * H)
    while True:
        tj = next(
            (
                t
                for t in col_tiles
                if _tiled_vmem_bytes(bt, t, ZA, D, H) <= _VMEM_WEIGHT_BUDGET_BYTES
            ),
            col_tiles[-1],
        )
        if _tiled_vmem_bytes(bt, tj, ZA, D, H) <= _VMEM_WEIGHT_BUDGET_BYTES:
            return bt, tj
        if bt > 8:
            bt = max(8, bt // 2)
            continue
        raise ValueError(
            f"fused RSSM tiled kernel cannot fit VMEM: D={D} H={H} ZA={ZA} "
            f"needs {_tiled_vmem_bytes(bt, tj, ZA, D, H) / 2**20:.1f} MiB at the "
            f"smallest tiling (budget {_VMEM_WEIGHT_BUDGET_BYTES / 2**20:.0f} MiB) "
            "— disable algo.world_model.recurrent_model.fused_pallas for this preset"
        )


def _pallas_forward_tiled(
    x, h, w_in, b_in, ln_in_scale, ln_in_bias, w_gru, gru_scale, gru_bias,
    block_b: int = 64,
    interpret: bool = False,
):
    from jax.experimental.pallas import tpu as pltpu

    B, ZA = x.shape
    H = h.shape[-1]
    D = w_in.shape[-1]
    f32 = jnp.float32
    x = x.astype(f32)
    h = h.astype(f32)
    w_in = w_in.astype(f32)
    b_in = b_in.reshape(1, D).astype(f32)
    ln_in_scale = ln_in_scale.reshape(1, D).astype(f32)
    ln_in_bias = ln_in_bias.reshape(1, D).astype(f32)
    w_gru = w_gru.astype(f32)
    gru_scale = gru_scale.reshape(1, 3 * H).astype(f32)
    gru_bias = gru_bias.reshape(1, 3 * H).astype(f32)

    bt, tj = _plan_tiled(B, ZA, D, H, block_b)
    pad = (-B) % bt
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        h = jnp.pad(h, ((0, pad), (0, 0)))
    grid = ((B + pad) // bt, (3 * H) // tj)

    out = pl.pallas_call(
        _rssm_kernel_tiled,
        out_shape=jax.ShapeDtypeStruct((B + pad, H), f32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, ZA), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, H), lambda i, j: (i, 0)),
            pl.BlockSpec((ZA, D), lambda i, j: (0, 0)),
            pl.BlockSpec((1, D), lambda i, j: (0, 0)),
            pl.BlockSpec((1, D), lambda i, j: (0, 0)),
            pl.BlockSpec((1, D), lambda i, j: (0, 0)),
            pl.BlockSpec((D + H, tj), lambda i, j: (0, j)),  # streamed
            pl.BlockSpec((1, 3 * H), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 3 * H), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bt, H), lambda i, j: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bt, D), f32),       # y (input projection)
            pltpu.VMEM((bt, 3 * H), f32),   # raw gate pre-activations
        ],
        interpret=interpret,
    )(x, h, w_in, b_in, ln_in_scale, ln_in_bias, w_gru, gru_scale, gru_bias)
    return out[:B]
