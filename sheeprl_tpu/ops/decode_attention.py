"""Pallas TPU kernel: one decode token an env against that env's attention cache, read only as far as the
env has written it.

A decode step of ``models/decoder.py`` attends with one query token an env to a cache of ``S`` positions of
which the env holds ``n = min(pos + 1, S)``: in a full cache, and in a ring before it wraps, the valid slots
are exactly ``[0, n)``; a wrapped ring holds ``S``.  XLA has no static shape for a bound that differs by env,
so its two products read all ``S`` positions of every env at every step.  This kernel takes ``n`` as
prefetched scalars and walks an env's cache in blocks of ``block`` positions: the block index of a grid step
is clamped to the env's last block, so past it no copy is issued (the pipeline fetches a block only when its
index changes), and the compute of those steps is skipped.  The tail of the last block is masked by position,
in the scores and in the values, so nothing past ``n`` reaches the result whatever the slots hold.

The mathematics and the precision are ``decoder._attend``'s: scores accumulated in float32 from the cache's
dtype, divided by ``sqrt(D)``, softmax in float32 (here with a running maximum and sum over the blocks), the
probabilities cast to the values' dtype before the second product, float32 accumulation.  No gradient: the
rollout is not differentiated (``decoder.segment``, which is, keeps ``_attend``).

Layout.  The cache is lane-dense, ``(B, S, KV * D)``, so a block is whole tiles whatever the head width.  The
heads share one product: the queries of an env are laid out block-diagonally, row ``(h, g)`` holding
``q[h, g]`` in the lanes of head ``h`` and nought elsewhere, so ``(KV * G, KV * D) x (KV * D, block)`` gives
every head's scores at once, exactly (the other heads add products with nought), and of the second product's
``(KV * G, KV * D)`` the diagonal blocks are the result.  The query groups are padded to whole sublane tiles
of 8 rows; a padded row attends evenly to the valid slots and is dropped.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

# Pallas is imported where the kernel is traced, as models.py imports gru_pallas.py: every program's start imports
# this module (the algorithm registry imports the decoder) and only a decode step with a long cache needs Pallas.

BLOCK = 512  # positions a grid step fetches: 512 KiB of keys and as much of values at 512 lanes of bfloat16
ROWS = 8  # a float32 sublane tile: the query group of a head is padded to whole tiles


def engages(cache_len: int, block: int = BLOCK) -> bool:
    """A cache of at least two whole blocks has blocks to skip; a shorter one has nothing to gain."""
    return cache_len >= 2 * block and cache_len % block == 0


def blocks_read(n: jax.Array, block: int = BLOCK) -> jax.Array:
    """Blocks the kernel fetches for an env that holds ``n`` positions."""
    return (n + block - 1) // block


def _kernel(n_ref, q_ref, k_ref, v_ref, o_ref, qd_ref, m_ref, l_ref, acc_ref, *, block: int, heads: int, head_dim: int):
    from jax.experimental import pallas as pl

    b, j = pl.program_id(0), pl.program_id(1)
    n = n_ref[b]
    rows, lanes = q_ref.shape  # the padded query group, KV * D

    def on_diagonal():
        head = jax.lax.broadcasted_iota(jnp.int32, (heads, rows, lanes), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (heads, rows, lanes), 2)
        return (lane >= head * head_dim) & (lane < (head + 1) * head_dim)

    @pl.when(j == 0)
    def _():
        q = jnp.broadcast_to(q_ref[...][None], (heads, rows, lanes))
        qd_ref[...] = jnp.where(on_diagonal(), q, 0.0).reshape(heads * rows, lanes).astype(qd_ref.dtype)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block < n)
    def _():
        scores = jax.lax.dot_general(
            qd_ref[...], k_ref[...], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        scores = scores / math.sqrt(head_dim)
        held = j * block + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1) < n
        scores = jnp.where(held, scores, -1e30)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(scores - m_next)
        v = v_ref[...]
        v = jnp.where(j * block + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) < n, v, jnp.zeros_like(v))
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_next

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o = (acc_ref[...] / l_ref[...]).reshape(heads, rows, lanes)
        o_ref[...] = jnp.where(on_diagonal(), o, 0.0).sum(axis=0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _ragged(q: jax.Array, k: jax.Array, v: jax.Array, n: jax.Array, block: int, interpret: bool) -> jax.Array:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, KV, G, D = q.shape
    S, lanes = k.shape[1:]
    rows = -(-G // ROWS) * ROWS
    # (B, rows, KV * D): the query group down the sublanes, the heads side by side in the lanes as the cache has them
    qg = jnp.moveaxis(q, 2, 1).reshape(B, G, lanes).astype(jnp.float32)
    qg = jnp.pad(qg, ((0, 0), (0, rows - G), (0, 0)))
    cache = pl.BlockSpec((None, block, lanes), lambda b, j, n_ref: (b, jnp.minimum(j, (n_ref[b] - 1) // block), 0))
    group = pl.BlockSpec((None, rows, lanes), lambda b, j, n_ref: (b, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, block=block, heads=KV, head_dim=D),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, S // block),
            in_specs=[group, cache, cache],
            out_specs=group,
            scratch_shapes=[
                pltpu.VMEM((KV * rows, lanes), k.dtype),  # the block-diagonal queries
                pltpu.VMEM((KV * rows, 1), jnp.float32),  # running maximum
                pltpu.VMEM((KV * rows, 1), jnp.float32),  # running sum
                pltpu.VMEM((KV * rows, lanes), jnp.float32),  # the second product so far
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, rows, lanes), v.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        name="decode_attention",
        interpret=interpret,
    )(n.astype(jnp.int32), qg, k, v)
    return jnp.moveaxis(out[:, :G].reshape(B, G, KV, D), 1, 2).reshape(B, KV * G * D)


def decode_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, n: jax.Array, block: int = BLOCK, interpret: Optional[bool] = None
) -> jax.Array:
    """``q`` (B, KV, G, D), ``k``/``v`` (B, S, KV * D), ``n`` (B,) valid slots an env (1 to S) -> (B, KV * G * D)
    in the values' dtype: what ``decoder._attend`` gives for one token under the mask ``slot < n``."""
    if not engages(k.shape[1], block):
        raise ValueError(f"a cache of {k.shape[1]} positions does not hold two whole blocks of {block}")
    if interpret is None:  # only the TPU has the Mosaic backend (as ops/gru_pallas.py decides it)
        interpret = jax.default_backend() != "tpu"
    return _ragged(q, k, v, n, block, interpret)
