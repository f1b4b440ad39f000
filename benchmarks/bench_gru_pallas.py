"""Benchmark the Pallas fused LayerNorm-GRU cell vs the plain XLA path on TPU.

The kernel was interpret-validated only; decide on
real hardware whether it wins (enable by default) or loses (remove the dead
fast-path).  Shapes cover the Dreamer presets' recurrent sizes
(S=512, M=1024, L=2048, XL=4096 — reference
sheeprl/algos/dreamer_v3/agent.py world-model sizes) at rollout (B=4/16) and
training (B=16*64 flattened scan step is B per step, so B=16) batch shapes.

Usage:  python benchmarks/bench_gru_pallas.py
Prints one JSON line per (H, B) with xla_us, pallas_us, speedup.
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.ops.gru_pallas import fused_layernorm_gru

# XLA baselines ARE the ops' reference math — one implementation, no drift
from sheeprl_tpu.ops.gru_pallas import _reference_math as _gru_reference
from sheeprl_tpu.ops.rssm_pallas import _reference_math as _rssm_reference

xla_layernorm_gru = jax.jit(_gru_reference)


def timeit(step, h0, iters=None, scan_len=None):
    """Per-step microseconds of ``h = step(h)`` iterated inside ``lax.scan``.

    The step runs under ``lax.scan`` in ONE jitted program per dispatch
    (``scan_len`` steps each) — eager per-call timing measures the host's
    dispatch rate, not a µs-scale kernel, and the scan is also exactly how
    the RSSM consumes these kernels in training.  Completion is bounded by
    ``device_sync``.  Outer dispatches are chained (data-dependent) and
    auto-scaled so the run dominates the per-dispatch floor."""
    from functools import partial

    from jax import lax

    from sheeprl_tpu.utils.utils import device_sync

    on_tpu = jax.default_backend() == "tpu"
    if scan_len is None:
        # interpret-mode pallas on CPU is a correctness path, not a perf
        # path — keep smoke runs short; real numbers need the TPU
        scan_len = 256 if on_tpu else 2
    scanned = jax.jit(
        partial(
            lambda n, h: lax.scan(lambda c, _: (step(c), None), h, None, length=n)[0],
            scan_len,
        )
    )
    h = scanned(h0)
    device_sync(h)
    calibrating = iters is None
    if calibrating:
        iters = 4 if on_tpu else 1
    t0 = time.perf_counter()
    h = h0
    for _ in range(iters):
        h = scanned(h)
    device_sync(h)
    dt = time.perf_counter() - t0
    if calibrating and on_tpu:
        # rescale until the chain dominates the sync floor
        attempts = 0
        while dt < 0.5 and iters < 100_000 and attempts < 6:
            iters = max(iters + 1, int(iters * 0.6 / max(dt, 1e-6)))
            t0 = time.perf_counter()
            h = h0
            for _ in range(iters):
                h = scanned(h)
            device_sync(h)
            dt = time.perf_counter() - t0
            attempts += 1
    return dt / (iters * scan_len) * 1e6  # us per step


def main():
    rng = np.random.default_rng(0)
    results = []
    for H in (512, 1024, 2048, 4096):
        D = H  # Dreamer uses dense-projected input of the same width
        for B in (4, 16, 64, 256):
            x = jnp.asarray(rng.normal(size=(B, D)).astype(np.float32))
            h = jnp.asarray(rng.normal(size=(B, H)).astype(np.float32))
            w = jnp.asarray(rng.normal(size=(D + H, 3 * H)).astype(np.float32) * 0.02)
            scale = jnp.ones((3 * H,), jnp.float32)
            bias = jnp.zeros((3 * H,), jnp.float32)

            ref = xla_layernorm_gru(x, h, w, scale, bias)
            try:
                got = fused_layernorm_gru(x, h, w, scale, bias)
            except ValueError as e:  # VMEM budget guard: S-class only
                print(json.dumps({"H": H, "B": B, "skipped": str(e)[:80]}), flush=True)
                continue
            err = float(jnp.max(jnp.abs(ref - got)))

            xla_us = timeit(lambda hh: xla_layernorm_gru(x, hh, w, scale, bias), h)
            pal_us = timeit(lambda hh: fused_layernorm_gru(x, hh, w, scale, bias), h)
            rec = {
                "H": H,
                "B": B,
                "xla_us": round(xla_us, 1),
                "pallas_us": round(pal_us, 1),
                "speedup": round(xla_us / pal_us, 3),
                "max_abs_err": err,
                "platform": jax.devices()[0].platform,
            }
            results.append(rec)
            print(json.dumps(rec), flush=True)
    wins = sum(1 for r in results if r["speedup"] > 1.05)
    print(json.dumps({"summary": f"gru: pallas wins {wins}/{len(results)} shapes"}))
    bench_fused_rssm()


def bench_fused_rssm():
    """Whole-recurrent-path kernel (ops/rssm_pallas.py) vs the two-matmul XLA
    path, at Dreamer preset shapes (D = dense_units, H = recurrent size)."""
    from sheeprl_tpu.ops.rssm_pallas import fused_rssm_recurrent

    xla_path = jax.jit(_rssm_reference)

    rng = np.random.default_rng(1)
    results = []
    # (D=dense_units, H=recurrent): S=(512,512), M=(640,1024), L=(768,2048)
    for D, H in ((512, 512), (640, 1024), (768, 2048)):
        ZA = H + 6  # stoch_flat + actions, ~H for the presets
        for B in (16, 64, 256):
            x = jnp.asarray(rng.normal(size=(B, ZA)).astype(np.float32))
            h = jnp.asarray(rng.normal(size=(B, H)).astype(np.float32))
            w_in = jnp.asarray(rng.normal(size=(ZA, D)).astype(np.float32) * 0.02)
            b_in = jnp.zeros((D,), jnp.float32)
            ls = jnp.ones((D,), jnp.float32)
            lb = jnp.zeros((D,), jnp.float32)
            w_gru = jnp.asarray(rng.normal(size=(D + H, 3 * H)).astype(np.float32) * 0.02)
            gs = jnp.ones((3 * H,), jnp.float32)
            gb = jnp.zeros((3 * H,), jnp.float32)
            args = (x, h, w_in, b_in, ls, lb, w_gru, gs, gb)
            ref = xla_path(*args)
            try:
                got = fused_rssm_recurrent(x, h, w_in, b_in, ls, lb, w_gru, gs, gb)
            except ValueError as e:  # VMEM budget guard: S-class only
                print(json.dumps({"D": D, "H": H, "B": B, "skipped": str(e)[:80]}), flush=True)
                continue
            err = float(jnp.max(jnp.abs(ref - got)))
            xla_us = timeit(lambda hh: xla_path(x, hh, w_in, b_in, ls, lb, w_gru, gs, gb), h)
            pal_us = timeit(
                lambda hh: fused_rssm_recurrent(x, hh, w_in, b_in, ls, lb, w_gru, gs, gb), h
            )
            rec = {
                "kernel": "fused_rssm",
                "D": D,
                "H": H,
                "B": B,
                "xla_us": round(xla_us, 1),
                "pallas_us": round(pal_us, 1),
                "speedup": round(xla_us / pal_us, 3),
                "max_abs_err": err,
                "platform": jax.devices()[0].platform,
            }
            results.append(rec)
            print(json.dumps(rec), flush=True)
    wins = sum(1 for r in results if r["speedup"] > 1.05)
    print(json.dumps({"summary": f"fused_rssm: pallas wins {wins}/{len(results)} shapes"}))


if __name__ == "__main__":
    main()
