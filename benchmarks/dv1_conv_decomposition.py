"""DV1 benchmark-row decomposition: WHERE does the update budget go?

VERDICT r4 weak #2: the DV1 wall-clock row's "residual ~2× XLA-CPU conv
gap" was asserted from one cProfile run.  This script measures it per-op:

1. builds DreamerV1 at the EXACT benchmark sizing (`dreamer_v1_benchmarks`:
   tiny model, B=50 × L=50 pixel sequences, the reference recipe);
2. times the full jitted world-model update and its components (conv
   encoder fwd+bwd, DeCNN decoder fwd+bwd, RSSM scan) with XLA
   `cost_analysis()` FLOPs → sustained GFLOP/s per component;
3. answers the layout question directly: the decoder-shaped conv
   microbenched as NHWC vs NCHW `dimension_numbers` at the same shapes.

Usage: JAX_PLATFORMS=cpu python benchmarks/dv1_conv_decomposition.py
Prints a markdown table.
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def _timed(fn, *args, n=5):
    """Median wall-time of n calls, blocking on the result via device_sync."""
    from sheeprl_tpu.utils.utils import device_sync

    device_sync(fn(*args))  # warm/compile
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        device_sync(fn(*args))
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def _flops(fn, *args) -> float:
    import jax

    try:
        a = jax.jit(fn).lower(*args).compile().cost_analysis()
        if isinstance(a, (list, tuple)):
            a = a[0]
        return float(a.get("flops", 0.0))
    except Exception:
        return 0.0


def main() -> int:
    from sheeprl_tpu.utils.utils import force_cpu_backend

    force_cpu_backend()
    import numpy as np

    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.algos.dreamer_v1.agent import GaussianWorldModel, build_agent
    from sheeprl_tpu.algos.dreamer_v1.dreamer_v1 import make_train_phase
    from sheeprl_tpu.config.compose import compose
    from sheeprl_tpu.parallel.fabric import build_fabric
    from gymnasium import spaces

    cfg = compose(
        [
            "exp=dreamer_v1_benchmarks",
            "env=dummy",
            "env.id=discrete_dummy",
            "fabric.accelerator=cpu",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[]",
            "print_config=False",
        ]
    )
    fabric = build_fabric(cfg)
    B = int(cfg.algo.per_rank_batch_size)
    L = int(cfg.algo.per_rank_sequence_length)
    obs_space = spaces.Dict({"rgb": spaces.Box(0, 255, (64, 64, 3), np.uint8)})
    world_model, actor, critic, params = build_agent(fabric, (4,), False, cfg, obs_space)
    wm_p = params["world_model"]

    rng = np.random.default_rng(0)
    frames = jnp.asarray(rng.normal(size=(L, B, 64, 64, 3)).astype(np.float32))
    rows = []

    # ---- conv encoder fwd+bwd at benchmark shapes -------------------------
    def enc_loss(p, x):
        return world_model.apply(p, {"rgb": x}, method=GaussianWorldModel.encode).sum()

    enc_g = jax.jit(jax.grad(enc_loss))
    t_enc = _timed(enc_g, wm_p, frames)
    f_enc = _flops(jax.grad(enc_loss), wm_p, frames)
    rows.append(("conv encoder fwd+bwd (L·B=2500 frames)", t_enc, f_enc))

    # ---- DeCNN decoder fwd+bwd --------------------------------------------
    stoch = world_model.stoch_flat
    rec = int(cfg.algo.world_model.recurrent_model.recurrent_state_size)
    latent = jnp.asarray(rng.normal(size=(L, B, stoch + rec)).astype(np.float32))

    def dec_loss(p, z):
        out = world_model.apply(p, z, method=GaussianWorldModel.decode)
        return out["rgb"].sum()

    dec_g = jax.jit(jax.grad(dec_loss, argnums=0))
    t_dec = _timed(dec_g, wm_p, latent)
    f_dec = _flops(jax.grad(dec_loss, argnums=0), wm_p, latent)
    rows.append(("DeCNN decoder fwd+bwd (2500 frames -> 64x64)", t_dec, f_dec))

    # ---- full world-model update (the real train component) ---------------
    wm_opt, actor_opt, critic_opt, opt_state = _dv1_optimizers(fabric, cfg, params)
    train_phase = make_train_phase(
        fabric, cfg, world_model, actor, critic, wm_opt, actor_opt, critic_opt,
        cnn_keys=("rgb",), mlp_keys=(), is_continuous=False,
    )
    block = {
        "rgb": jnp.asarray(rng.integers(0, 255, (1, L, B, 64, 64, 3)).astype(np.uint8)),
        "actions": jnp.asarray(rng.integers(0, 2, (1, L, B, 4)).astype(np.float32)),
        "rewards": jnp.asarray(rng.normal(size=(1, L, B)).astype(np.float32)),
        "terminated": jnp.zeros((1, L, B), jnp.float32),
        "is_first": jnp.zeros((1, L, B), jnp.float32),
    }

    def one_update(p, o, b):
        return train_phase(p, o, b, jax.random.PRNGKey(0), jnp.int32(0))

    # donation: the train phase donates params/opt-state, so give every
    # timed call its own copies; time with n=3
    def run_update():
        p = jax.tree.map(jnp.copy, params)
        o = jax.tree.map(jnp.copy, opt_state)
        return one_update(p, o, block)

    t_full = _timed(run_update, n=3)
    rows.append(("FULL train update (WM + behavior, one dispatch)", t_full, 0.0))

    # ---- layout A/B: decoder-shaped transposed conv NHWC vs NCHW ----------
    # the heaviest decoder layer: upsample to 64x64 with tiny channels
    x_nhwc = jnp.asarray(rng.normal(size=(2500, 32, 32, 4)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(4, 4, 4, 2)).astype(np.float32))  # HWIO

    pad = [(2, 2), (2, 2)]  # 4x4 kernel, stride-2 transposed conv -> exact 2x upsample

    def conv_nhwc(x, k):
        return jax.lax.conv_general_dilated(
            x, k, window_strides=(1, 1), padding=pad,
            lhs_dilation=(2, 2),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )

    x_nchw = jnp.transpose(x_nhwc, (0, 3, 1, 2))
    k_oihw = jnp.transpose(k, (3, 2, 0, 1))

    def conv_nchw(x, k):
        return jax.lax.conv_general_dilated(
            x, k, window_strides=(1, 1), padding=pad,
            lhs_dilation=(2, 2),
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
        )

    t_nhwc = _timed(jax.jit(conv_nhwc), x_nhwc, k)
    t_nchw = _timed(jax.jit(conv_nchw), x_nchw, k_oihw)
    rows.append(("layout A/B: upsampling conv NHWC", t_nhwc, _flops(conv_nhwc, x_nhwc, k)))
    rows.append(("layout A/B: upsampling conv NCHW", t_nchw, _flops(conv_nchw, x_nchw, k_oihw)))

    # ---- report -----------------------------------------------------------
    print("\n| component | time | GFLOP | GFLOP/s |")
    print("|---|---|---|---|")
    for name, t, f in rows:
        gfs = f / t / 1e9 if f else 0.0
        print(
            f"| {name} | {t * 1e3:.1f} ms | "
            f"{f / 1e9:.2f} | {gfs:.1f} |" if f else f"| {name} | {t * 1e3:.1f} ms | — | — |"
        )
    print(
        f"\nlayout verdict: NCHW/NHWC = {t_nchw / t_nhwc:.2f}x "
        f"({'NHWC wins — layout is NOT the gap' if t_nhwc <= t_nchw else 'NCHW faster — layout IS the gap'})"
    )
    return 0


def _dv1_optimizers(fabric, cfg, params):
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import build_dv3_optimizers

    return build_dv3_optimizers(fabric, cfg, params)


if __name__ == "__main__":
    sys.exit(main())
