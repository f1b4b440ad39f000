"""The ragged decode-attention kernel against ``decoder._attend`` under the mask of ``slot_positions``
(interpret mode, no TPU): both head shapes of the token cells, float32 and bfloat16, per-env positions on
every edge of a block and of a ring."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import decoder
from sheeprl_tpu.ops import decode_attention as da

TRINITY, LFM2 = (4, 8, 128), (8, 4, 64)  # key-value heads, queries a head, head width
BLOCK, SIZE = 128, 512
# nothing written but the token itself (a reset in the step leaves an env here, its slots full of the old episode);
# one under, exactly on and one over a block's end; the last slot; a ring that has wrapped, once and often
EDGES = (0, BLOCK - 2, BLOCK - 1, BLOCK, SIZE - 1, SIZE, 3 * SIZE + 17, 301)
TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-5), jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def inputs(shape, dtype, size=SIZE, positions=EDGES, seed=0):
    KV, G, D = shape
    B = len(positions)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (B, KV, G, D), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (B, size, KV, D), jnp.float32).astype(dtype)
    v = jax.random.normal(kv, (B, size, KV, D), jnp.float32).astype(dtype)
    return q, k, v, jnp.asarray(positions, jnp.int32)


def plain(q, k, v, pos):
    mask = decoder.slot_positions(pos, k.shape[1]) >= 0
    return decoder._attend(q[:, None], k, v, mask[:, None])[:, 0]


def ragged(q, k, v, pos, block=BLOCK):
    B, S = k.shape[:2]
    return da.decode_attention(q, k.reshape(B, S, -1), v.reshape(B, S, -1), jnp.minimum(pos + 1, S), block=block, interpret=True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [TRINITY, LFM2], ids=["4x8x128", "8x4x64"])
def test_the_kernel_matches_attend_on_every_edge(shape, dtype):
    q, k, v, pos = inputs(shape, dtype)
    want, got = plain(q, k, v, pos), ragged(q, k, v, pos)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("shape", [TRINITY, LFM2], ids=["4x8x128", "8x4x64"])
def test_nothing_past_an_env_s_length_reaches_the_result(shape):
    """Slots an env has not written hold NaN, in its last block's tail and in every block after it."""
    q, k, v, pos = inputs(shape, jnp.float32)
    unwritten = (decoder.slot_positions(pos, SIZE) < 0)[:, :, None, None]
    assert unwritten[:4].any(axis=(1, 2, 3)).all() and not unwritten[5:7].any()  # a wrapped ring holds every slot
    got = ragged(q, jnp.where(unwritten, jnp.nan, k), jnp.where(unwritten, jnp.nan, v), pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(plain(q, k, v, pos)), **TOL[jnp.float32])


def test_the_kernel_s_own_block_on_a_cache_of_two():
    """At the block the decoder runs it with, on the shortest cache that engages it (a ring of Trinity's)."""
    size = 2 * da.BLOCK
    q, k, v, pos = inputs(TRINITY, jnp.bfloat16, size=size, positions=(0, da.BLOCK - 1, da.BLOCK, size - 1, size + 5), seed=3)
    want, got = plain(q, k, v, pos), ragged(q, k, v, pos, block=da.BLOCK)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), **TOL[jnp.bfloat16])


def test_which_caches_engage_and_what_they_fetch():
    assert da.engages(8192) and da.engages(2 * da.BLOCK) and not da.engages(2 * da.BLOCK - 1)
    assert not da.engages(da.BLOCK) and not da.engages(32) and not da.engages(2 * da.BLOCK + 8)  # whole blocks only
    n = jnp.asarray([1, BLOCK - 1, BLOCK, BLOCK + 1, SIZE])
    assert da.blocks_read(n, BLOCK).tolist() == [1, 1, 1, 2, 4]
    with pytest.raises(ValueError, match="two whole blocks"):
        da.decode_attention(jnp.zeros((1, 2, 2, 16)), jnp.zeros((1, 32, 32)), jnp.zeros((1, 32, 32)), jnp.ones((1,), jnp.int32))
