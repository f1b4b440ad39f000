"""window_scan: trace-time unrolling must be semantically identical to scan.

Background: XLA-CPU runs convolution-bearing update
bodies ~5x slower inside ``lax.scan``'s outlined call, and ``unroll=True``
does not remove the penalty — only true trace-time inlining does.  The
helper must therefore agree with ``lax.scan`` exactly, on every path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.utils.utils import window_scan


def _body(carry, x):
    new = carry * 0.9 + x["a"].sum() + x["b"]
    return new, {"y": new * 2.0, "z": new - 1.0}


@pytest.mark.parametrize("U", [1, 3, 16])
def test_unrolled_matches_scan(U):
    xs = {
        "a": jnp.arange(U * 6, dtype=jnp.float32).reshape(U, 6),
        "b": jnp.linspace(0.0, 1.0, U),
    }
    c0 = jnp.float32(2.0)
    c_scan, ys_scan = jax.lax.scan(_body, c0, xs)
    c_ws, ys_ws = jax.jit(lambda c, x: window_scan(_body, c, x))(c0, xs)
    np.testing.assert_allclose(np.asarray(c_ws), np.asarray(c_scan), rtol=1e-6)
    for k in ys_scan:
        assert ys_ws[k].shape == ys_scan[k].shape
        np.testing.assert_allclose(np.asarray(ys_ws[k]), np.asarray(ys_scan[k]), rtol=1e-6)


def test_long_window_falls_back_to_scan():
    U = 40  # > unroll_limit: must take the lax.scan path (same semantics)
    xs = {"a": jnp.ones((U, 2)), "b": jnp.ones((U,))}
    c_scan, ys_scan = jax.lax.scan(_body, jnp.float32(0.0), xs)
    c_ws, ys_ws = window_scan(_body, jnp.float32(0.0), xs)
    np.testing.assert_allclose(np.asarray(c_ws), np.asarray(c_scan), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ys_ws["y"]), np.asarray(ys_scan["y"]), rtol=1e-6)


def test_respects_custom_unroll_limit():
    xs = {"a": jnp.ones((4, 2)), "b": jnp.ones((4,))}
    c_scan, _ = jax.lax.scan(_body, jnp.float32(1.0), xs)
    c_ws, _ = window_scan(_body, jnp.float32(1.0), xs, unroll_limit=2)  # forces scan path
    np.testing.assert_allclose(np.asarray(c_ws), np.asarray(c_scan), rtol=1e-6)
