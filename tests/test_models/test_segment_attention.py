"""The ragged segment-attention kernels against ``decoder._attention_probs`` and ``decoder._weigh`` on the same masked
inputs (interpret mode, no TPU): Keye's head shape, float32 and bfloat16, each env written to another length (none,
inside the first block, one either side of a block's edge, all of it), random selections, a query block that
selected nothing, and a segment that is not a whole number of query blocks."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import decoder
from sheeprl_tpu.ops import segment_attention as sa

KV, G, D = 4, 8, 128  # Keye's key-value heads, queries a head, head width
BLOCK, SIZE, T = 128, 512, 72  # two query blocks of 64, the second padded
WRITTEN = (0, 37, BLOCK - 1, BLOCK, BLOCK + 1, SIZE)
TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-5), jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}
DTYPES = pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])


def inputs(dtype, seed=0):
    """q (B, T, KV, G, D), keys and values per head (B, S, KV, D), and a selection of about a third of each env's
    written positions a query; the third env's first query block selects nothing, as a query after a reset does."""
    B = len(WRITTEN)
    kq, kk, kv, ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(kq, (B, T, KV, G, D), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (B, SIZE, KV, D), jnp.float32).astype(dtype)
    v = jax.random.normal(kv, (B, SIZE, KV, D), jnp.float32).astype(dtype)
    written = jnp.arange(SIZE)[None, None] < jnp.asarray(WRITTEN)[:, None, None]
    sel = (jax.random.uniform(ks, (B, T, SIZE)) < 0.3) & written
    return q, k, v, sel.at[2, :64].set(False)


def rows(cache):
    return cache.reshape(cache.shape[:2] + (-1,))


def plain(q, k, v, sel):
    """The decoder's own masked product over the prefix: (B, T, KV * G * D) output in the values' dtype, and the
    log-sum-exp (B, KV, G, T), -inf where a query selected nothing (its output is then nought)."""
    probs = decoder._attention_probs(q, k, sel)
    some = sel.any(axis=-1)
    o = jnp.where(some[..., None], decoder._weigh(probs, v), 0)
    scores = jnp.einsum("btkgd,bskd->bkgts", q, k, preferred_element_type=jnp.float32) / math.sqrt(D)
    lse = jax.nn.logsumexp(jnp.where(sel[:, None, None], scores, -jnp.inf), axis=-1)
    return o, lse, probs


def kernel(q, k, v, sel, block=BLOCK):
    o, lse = sa.attend(q, rows(k), rows(v), sel, block=block, interpret=True)
    return o.reshape(o.shape[:2] + (-1,)), jnp.transpose(lse, (0, 2, 3, 1))


def close(got, want, dtype):
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), **TOL[dtype])


@DTYPES
def test_the_output_matches_the_masked_product(dtype):
    q, k, v, sel = inputs(dtype)
    want, _, _ = plain(q, k, v, sel)
    got, _ = kernel(q, k, v, sel)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    close(got, want, dtype)
    assert not np.asarray(got[0]).any() and not np.asarray(got[2, :64]).any()  # nothing selected: nought


@DTYPES
def test_the_log_sum_exp_matches_and_is_minus_infinity_where_nothing_was_selected(dtype):
    q, k, v, sel = inputs(dtype)
    _, want, _ = plain(q, k, v, sel)
    _, got = kernel(q, k, v, sel)
    assert (np.isfinite(np.asarray(got)) == np.isfinite(np.asarray(want))).all()
    finite = np.isfinite(np.asarray(want))
    close(np.asarray(got)[finite], np.asarray(want)[finite], dtype)


@DTYPES
def test_the_head_mean_of_the_probabilities_matches(dtype):
    """From the prefix's own log-sum-exp, as L_I reads the merged one: the heads' mean of the softmax."""
    q, k, v, sel = inputs(dtype)
    _, lse, probs = plain(q, k, v, sel)
    want = jnp.where(sel.any(axis=-1)[..., None], jnp.mean(probs, axis=(1, 2)), 0.0)
    got = sa.head_mean(q, rows(k), sel, jnp.transpose(lse, (0, 3, 1, 2)), block=BLOCK, interpret=True)
    assert got.shape == sel.shape and got.dtype == jnp.float32
    close(got, want, dtype)
    assert not np.asarray(got)[~np.asarray(sel)].any()


@DTYPES
def test_the_queries_gradient_matches_with_a_cotangent_on_the_log_sum_exp(dtype):
    q, k, v, sel = inputs(dtype)
    some = sel.any(axis=-1)  # (B, T)
    ko, kl = jax.random.split(jax.random.PRNGKey(7))
    w_o = jax.random.normal(ko, (len(WRITTEN), T, KV * G * D))
    w_l = jax.random.normal(kl, (len(WRITTEN), KV, G, T))
    mask = some[:, None, None]

    def loss_plain(q):
        o, _, _ = plain(q, k, v, sel)
        scores = jnp.einsum("btkgd,bskd->bkgts", q, k, preferred_element_type=jnp.float32) / math.sqrt(D)
        lse = jax.nn.logsumexp(jnp.where(sel[:, None, None], scores, -1e30), axis=-1)
        return jnp.sum(o.astype(jnp.float32) * w_o) + jnp.sum(jnp.where(mask, lse, 0.0) * w_l)

    def loss_kernel(q):
        o, lse = kernel(q, k, v, sel)
        return jnp.sum(o * w_o) + jnp.sum(jnp.where(mask, lse, 0.0) * w_l)

    want, got = jax.grad(loss_plain)(q), jax.grad(loss_kernel)(q)
    assert got.dtype == q.dtype
    scale = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
    close(got.astype(jnp.float32) / scale, want.astype(jnp.float32) / scale, dtype)


def test_what_the_kernels_visit_and_which_prefixes_engage():
    _, _, _, sel = inputs(jnp.float32)
    seen = sa.visited(sel, BLOCK)
    assert seen.shape == (len(WRITTEN), 2, SIZE // BLOCK)
    # per env the blocks up to its written length (none for an empty prefix), and none for a query block that selected nothing
    assert seen[:, 1].sum(axis=-1).tolist() == [0, 1, 1, 1, 2, 4] and not seen[2, 0].any()
    assert int(sa.blocks_read(sel, BLOCK)) == 9
    assert sa.engages(32768) and sa.engages(2 * sa.BLOCK) and not sa.engages(2 * sa.BLOCK - 1)
    assert not sa.engages(sa.BLOCK) and not sa.engages(32) and not sa.engages(2 * sa.BLOCK + 8)
    with pytest.raises(ValueError, match="two whole blocks"):
        sa.attend(jnp.zeros((1, 8, 2, 2, 16)), jnp.zeros((1, 32, 32)), jnp.zeros((1, 32, 32)), jnp.ones((1, 8, 32), bool))
