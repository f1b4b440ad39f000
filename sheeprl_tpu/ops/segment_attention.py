"""Pallas TPU kernels: a segment's queries against each env's cached prefix under a learned sparse layer's selection,
read only where some query selected a key.

``decoder.segment`` runs ``T`` tokens an env whose keys are the carry's prefix (``S`` positions, constants: the
carry is stop-gradiented) followed by the segment's own.  A sparse layer's query attends over the keys its indexer
selected (``sel``): at most ``index_topk`` of the positions its env has written, which is ``[0, n)`` in a cache of
``max_len`` (slot = position).  XLA has no static shape for that, so a product over all ``S`` keys of every query,
masked afterwards, is what it can run.  These kernels walk an env's prefix in blocks of ``block`` positions and a
query block's rows in one grid step each: a key block in which no query of the query block selected a key is
neither fetched nor computed (``visited``: that flag is false past the env's written length, since nothing there is
selected, so the walk stops at the env's last written block).  The block index of a grid step is the last visited
block at or before it, so past it no copy is issued (the pipeline fetches a block only when its index changes).

The mathematics and the precision are ``decoder._attend``'s over the prefix's columns: scores accumulated in
float32 from the cache's dtype, divided by ``sqrt(D)``, ``sel`` as the mask, a softmax in float32 (here with a running
maximum and sum over the blocks), the probabilities cast to the values' dtype before the second product, float32
accumulation.  :func:`attend` gives the prefix's part normalised over the prefix alone and its log-sum-exp per
(env, query, head), so that a caller merges it with the segment's own keys into the same softmax over the same
selected set; its gradient reaches the queries only (the keys and values are constants) and takes the cotangent of
the log-sum-exp too.  :func:`head_mean` gives the probabilities of the merged softmax over the prefix, summed over
the heads and divided by their number (what L_I reads, a constant), from the merged log-sum-exp.

Layout.  The cache is read as stored, lane-dense ``(B, S, KV * D)``: a key block is whole tiles and holds every
head.  The queries are laid out ``(B, KV, G, T, D)``, so that for each key-value head the ``G x query-block`` rows
are one operand of the MXU against that head's ``D`` lanes of the block.  A row's statistics travel to and from HBM
broadcast over ``LANES`` lanes.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# Pallas is imported where a kernel is traced, as decode_attention.py does: only a segment over a long sparse cache
# needs it.

BLOCK = 512  # positions a key block holds: 512 KiB of keys and as much of values at 512 lanes of bfloat16
QUERY_BLOCK = 64  # queries of an env a grid step takes: G x 64 rows a key-value head
LANES = 128  # a row's maximum and sum are kept broadcast over one vreg's lanes
MASKED = -1e30  # a score that was not selected, as ``decoder._attention_probs`` masks it


def _block(block: Optional[int]) -> int:
    return BLOCK if block is None else block  # read when called: a test sets a smaller block here


def engages(cache_len: int, block: Optional[int] = None) -> bool:
    """A prefix of at least two whole blocks has blocks to skip; a shorter one has nothing to gain."""
    block = _block(block)
    return cache_len >= 2 * block and cache_len % block == 0


def _query_block(T: int) -> int:
    return min(QUERY_BLOCK, -(-T // 8) * 8)


def visited(sel: jax.Array, block: Optional[int] = None) -> jax.Array:
    """``sel`` (B, T, S) -> (B, query blocks, key blocks) bool: some query of the block selected a key of the block."""
    B, T, S = sel.shape
    block = _block(block)
    tq = _query_block(T)
    sel = jnp.pad(sel, ((0, 0), (0, -T % tq), (0, 0)))
    return sel.reshape(B, sel.shape[1] // tq, tq, S // block, block).any(axis=(2, 4))


def blocks_read(sel: jax.Array, block: Optional[int] = None) -> jax.Array:
    """Key blocks of the envs' prefixes that some query selected from, once an env (the kernels fetch such a block
    once for each query block that selected from it)."""
    return jnp.sum(jnp.any(visited(sel, block), axis=1), dtype=jnp.int32)


def _fetch(seen: jax.Array) -> jax.Array:
    """The key block each grid step holds: the last visited one at or before it (-1 before the first), flattened."""
    j = jnp.arange(seen.shape[-1], dtype=jnp.int32)
    return jax.lax.cummax(jnp.where(seen, j, -1), axis=seen.ndim - 1).reshape(-1)


def _specs(KV: int, G: int, tq: int, D: int, block: int, lanes: int):
    """BlockSpecs over the grid (env, query block, key block) with the flattened fetch table prefetched."""
    from jax.experimental import pallas as pl

    def step(b, i, j, fetch):  # the key block this grid step holds
        nq, nk = pl.num_programs(1), pl.num_programs(2)
        return jnp.maximum(fetch[(b * nq + i) * nk + j], 0)

    rows = pl.BlockSpec((None, KV, G, tq, D), lambda b, i, j, fetch: (b, 0, 0, i, 0))
    stats = pl.BlockSpec((None, KV, G, tq, LANES), lambda b, i, j, fetch: (b, 0, 0, i, 0))
    cache = pl.BlockSpec((None, block, lanes), lambda b, i, j, fetch: (b, step(b, i, j, fetch), 0))
    mask = pl.BlockSpec((None, tq, block), lambda b, i, j, fetch: (b, i, step(b, i, j, fetch)))
    return rows, stats, cache, mask


def _every_block(tq: int, block: int):
    """A (query block, key block) output that every grid step writes, visited or not."""
    from jax.experimental import pallas as pl

    return pl.BlockSpec((None, tq, block), lambda b, i, j, fetch: (b, i, j))


def _is_visited(fetch_ref):
    from jax.experimental import pallas as pl

    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    return fetch_ref[(b * pl.num_programs(1) + i) * pl.num_programs(2) + j] == j


def _scores(q_ref, k_ref, chosen, h: int, head_dim: int):
    """Head ``h``'s scores of the block, (G, tq, block) float32, the keys not chosen at ``MASKED``."""
    G, tq = q_ref.shape[1:3]
    q = q_ref[h].reshape(G * tq, head_dim)
    k = k_ref[:, h * head_dim:(h + 1) * head_dim]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    s = (s / math.sqrt(head_dim)).reshape(G, tq, k.shape[0])
    return jnp.where(chosen[None], s, MASKED)


def _row(stat_ref, h: int):
    """A row statistic of head ``h``, (G, tq, 1), from its copies over the lanes."""
    return jnp.max(stat_ref[h], axis=-1, keepdims=True)


def _forward_kernel(fetch_ref, q_ref, k_ref, v_ref, sel_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *, head_dim: int):
    from jax.experimental import pallas as pl

    KV, G, tq = q_ref.shape[:3]

    @pl.when(pl.program_id(2) == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_is_visited(fetch_ref))
    def _():
        chosen = sel_ref[...] != 0
        for h in range(KV):
            s = _scores(q_ref, k_ref, chosen, h, head_dim)
            m_prev = m_ref[h]
            m_next = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.where(chosen[None], jnp.exp(s - m_next), 0.0)
            v = v_ref[:, h * head_dim:(h + 1) * head_dim]
            pv = jnp.dot(p.reshape(G * tq, -1).astype(v.dtype), v, preferred_element_type=jnp.float32)
            l_ref[h] = alpha * l_ref[h] + p.sum(axis=-1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + pv.reshape(G, tq, head_dim)
            m_ref[h] = m_next

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        l = l_ref[...]
        some = l > 0  # a row with nothing selected on the prefix gives nought and a log-sum-exp of -inf
        o_ref[...] = jnp.where(some, acc_ref[...] / jnp.where(some, l, 1.0), 0.0)
        lse = jnp.where(some, m_ref[...] + jnp.log(jnp.where(some, l, 1.0)), -jnp.inf)
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


def _backward_kernel(fetch_ref, q_ref, k_ref, v_ref, sel_ref, lse_ref, delta_ref, do_ref, dq_ref, acc_ref, *, head_dim: int):
    from jax.experimental import pallas as pl

    KV, G, tq = q_ref.shape[:3]

    @pl.when(pl.program_id(2) == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_is_visited(fetch_ref))
    def _():
        chosen = sel_ref[...] != 0
        for h in range(KV):
            s = _scores(q_ref, k_ref, chosen, h, head_dim)
            p = jnp.where(chosen[None], jnp.exp(s - _row(lse_ref, h)), 0.0)
            k = k_ref[:, h * head_dim:(h + 1) * head_dim]
            v = v_ref[:, h * head_dim:(h + 1) * head_dim]
            dp = jax.lax.dot_general(do_ref[h].reshape(G * tq, head_dim), v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32).reshape(G, tq, -1)
            ds = p * (dp - _row(delta_ref, h))
            dq = jnp.dot(ds.reshape(G * tq, -1).astype(k.dtype), k, preferred_element_type=jnp.float32)
            acc_ref[h] = acc_ref[h] + dq.reshape(G, tq, head_dim)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        dq_ref[...] = acc_ref[...] / math.sqrt(head_dim)


def _head_mean_kernel(fetch_ref, q_ref, k_ref, sel_ref, lse_ref, p_ref, *, head_dim: int):
    from jax.experimental import pallas as pl

    KV, G = q_ref.shape[:2]

    @pl.when(_is_visited(fetch_ref))
    def _():
        chosen = sel_ref[...] != 0
        total = jnp.zeros(p_ref.shape, jnp.float32)
        for h in range(KV):
            s = _scores(q_ref, k_ref, chosen, h, head_dim)
            total = total + jnp.where(chosen[None], jnp.exp(s - _row(lse_ref, h)), 0.0).sum(axis=0)
        p_ref[...] = total / (KV * G)

    @pl.when(jnp.logical_not(_is_visited(fetch_ref)))
    def _():
        p_ref[...] = jnp.zeros_like(p_ref)


def _grid_call(kernel, grid, in_specs, out_specs, out_shape, scratch, interpret: bool, name: str):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        name=name,
        interpret=interpret,
    )


def _forward(qh, k, v, sel, fetch, block: int, interpret: bool):
    from jax.experimental.pallas import tpu as pltpu

    B, KV, G, Tp, D = qh.shape
    tq = _query_block(Tp)
    rows, stats, cache, mask = _specs(KV, G, tq, D, block, k.shape[2])
    f32 = jnp.float32
    call = _grid_call(
        functools.partial(_forward_kernel, head_dim=D), (B, Tp // tq, k.shape[1] // block),
        [rows, cache, cache, mask], [rows, stats],
        [jax.ShapeDtypeStruct(qh.shape, f32), jax.ShapeDtypeStruct((B, KV, G, Tp, LANES), f32)],
        [pltpu.VMEM((KV, G, tq, 1), f32), pltpu.VMEM((KV, G, tq, 1), f32), pltpu.VMEM((KV, G, tq, D), f32)],
        interpret, "segment_attention")
    o, lse = call(fetch, qh, k, v, sel)
    return o, lse[..., 0]


def _backward(qh, k, v, sel, fetch, lse, delta, do, block: int, interpret: bool):
    from jax.experimental.pallas import tpu as pltpu

    B, KV, G, Tp, D = qh.shape
    tq = _query_block(Tp)
    rows, stats, cache, mask = _specs(KV, G, tq, D, block, k.shape[2])
    lanes = lambda z: jnp.broadcast_to(z[..., None], z.shape + (LANES,))  # noqa: E731
    call = _grid_call(
        functools.partial(_backward_kernel, head_dim=D), (B, Tp // tq, k.shape[1] // block),
        [rows, cache, cache, mask, stats, stats, rows], rows, jax.ShapeDtypeStruct(qh.shape, jnp.float32),
        [pltpu.VMEM((KV, G, tq, D), jnp.float32)], interpret, "segment_attention_dq")
    return call(fetch, qh, k, v, sel, lanes(lse), lanes(delta), do.astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _prefix(qh, k, v, sel, fetch, block: int, interpret: bool):
    return _forward(qh, k, v, sel, fetch, block, interpret)


def _prefix_fwd(qh, k, v, sel, fetch, block, interpret):
    o, lse = _forward(qh, k, v, sel, fetch, block, interpret)
    return (o, lse), (qh, k, v, sel, fetch, o, lse)


def _prefix_bwd(block, interpret, res, cot):
    """Only the queries take a gradient: ``ds = p (do . v - (do . o - dlse))``, ``dq = ds k / sqrt(D)``."""
    qh, k, v, sel, fetch, o, lse = res
    do, dlse = cot
    delta = jnp.sum(do * o, axis=-1) - dlse
    safe = jnp.where(jnp.isfinite(lse), lse, 0.0)  # a row with nothing selected has no probability to recompute
    dq = _backward(qh, k, v, sel, fetch, safe, delta, do, block, interpret)
    return dq.astype(qh.dtype), None, None, None, None


_prefix.defvjp(_prefix_fwd, _prefix_bwd)


def _layout(q: jax.Array, sel: jax.Array):
    """q (B, T, KV, G, D) -> (B, KV, G, Tp, D) and sel (B, T, S) -> int8 (B, Tp, S), T padded to whole query blocks
    (a padded row selects nothing)."""
    T = q.shape[1]
    pad = -T % _query_block(T)
    qh = jnp.pad(jnp.transpose(q, (0, 2, 3, 1, 4)), ((0, 0),) * 3 + ((0, pad), (0, 0)))
    return qh, jnp.pad(sel.astype(jnp.int8), ((0, 0), (0, pad), (0, 0)))


def _interpret(interpret: Optional[bool]) -> bool:
    return jax.default_backend() != "tpu" if interpret is None else interpret  # only the TPU has the Mosaic backend


def _check(cache: jax.Array, block: Optional[int]) -> int:
    block = _block(block)
    if not engages(cache.shape[1], block):
        raise ValueError(f"a prefix of {cache.shape[1]} positions does not hold two whole blocks of {block}")
    return block


def attend(
    q: jax.Array, keys: jax.Array, values: jax.Array, sel: jax.Array, block: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """``q`` (B, T, KV, G, D), ``keys``/``values`` (B, S, KV * D) constants, ``sel`` (B, T, S) bool -> the queries' attention
    over the selected keys of the prefix, normalised over them, (B, T, KV, G, D) float32, and its log-sum-exp (B, T,
    KV, G) float32 (-inf, and an output of nought, where a query selected nothing there).  Differentiable in ``q``."""
    block = _check(keys, block)
    T = q.shape[1]
    qh, sel8 = _layout(q, sel)
    o, lse = _prefix(qh, keys, values, sel8, _fetch(visited(sel, block)), block, _interpret(interpret))
    return jnp.transpose(o, (0, 3, 1, 2, 4))[:, :T], jnp.transpose(lse, (0, 3, 1, 2))[:, :T]


def head_mean(
    q: jax.Array, keys: jax.Array, sel: jax.Array, lse: jax.Array, block: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """The probabilities over the prefix of a softmax whose log-sum-exp per (env, query, head) is ``lse`` (B, T, KV,
    G), averaged over the heads: (B, T, S) float32, nought at every key not selected.  No gradient."""
    block = _check(keys, block)
    B, T = q.shape[:2]
    qh, sel8 = _layout(jax.lax.stop_gradient(q), sel)
    KV, G, Tp, D = qh.shape[1:]
    tq = _query_block(Tp)
    rows, stats, cache, mask = _specs(KV, G, tq, D, block, keys.shape[2])
    lse = jax.lax.stop_gradient(lse)
    safe = jnp.where(jnp.isfinite(lse), lse, 0.0).astype(jnp.float32)
    lse_h = jnp.pad(jnp.transpose(safe, (0, 2, 3, 1)), ((0, 0),) * 3 + ((0, Tp - T),))
    call = _grid_call(
        functools.partial(_head_mean_kernel, head_dim=D), (B, Tp // tq, keys.shape[1] // block),
        [rows, cache, mask, stats], _every_block(tq, block), jax.ShapeDtypeStruct((B, Tp, keys.shape[1]), jnp.float32), [],
        _interpret(interpret), "segment_head_mean")
    p = call(_fetch(visited(sel, block)), qh, jax.lax.stop_gradient(keys), sel8,
             jnp.broadcast_to(lse_h[..., None], lse_h.shape + (LANES,)))
    return jax.lax.stop_gradient(p[:, :T])
