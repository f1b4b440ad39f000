"""Benchmark harness (driver contract: prints ONE JSON line).

Default benchmark: **DreamerV3-S gradient-update throughput** — the
north-star workload (BASELINE.json: DreamerV3 Atari-100K).  The reference
trains MsPacman-100K in 14h on an RTX 3080 (BASELINE.md): 100K frames at
action_repeat 4 → 25K policy steps, replay_ratio 1 → ~25K gradient updates,
i.e. ~0.5 updates/s.  Each update processes a 16×64 sequence batch.  This
bench times the SAME work unit — full DreamerV3-S updates (world model +
imagination + actor + critic + EMA) on 64×64×3 pixel sequences — on the
available accelerator, after one warmup dispatch.

``BENCH_TARGET=ppo`` switches to the PPO CartPole wall-clock benchmark
(reference: 81.27 s, BASELINE.md).
"""

from __future__ import annotations

import json
import os
import sys
import time

BASELINE_DV3_UPDATES_PER_S = 0.5   # RTX 3080, MsPacman-100K (BASELINE.md)

# Reference v0.5.5 published wall-clocks, 4-CPU Lightning Studio host
# (/root/reference/README.md:83-189): exp=<algo>_benchmarks.  The `_wall`
# dreamer targets run the reference's 16384-step tiny-model benchmark config
# (the README "1 device" rows); on hosts without ALE the MsPacman env must be
# swapped via BENCH_ARGS, which voids vs_baseline automatically.
BASELINE_CPU_WALL_CLOCK_S = {
    "ppo": 81.27,            # CartPole-v1, 1 env, 65536 steps
    "a2c": 84.76,            # CartPole-v1, 1 env, 65536 steps
    "sac": 320.21,           # LunarLanderContinuous, 4 envs, 65536 steps
    "dreamer_v1_wall": 2207.13,  # MsPacman tiny model, 16384 steps
    "dreamer_v2_wall": 906.42,
    "dreamer_v3_wall": 1589.30,
}


def _git_sha() -> str | None:
    """The repo HEAD this bench ran against (best-effort — a payload missing
    its SHA is a warning sign, not a crash)."""
    import subprocess

    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10,
        )
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else None
    except Exception:
        return None


def _bench_stamp(target: str) -> dict:
    """Self-describing provenance every mode stamps into its JSON payload:
    BENCH_*.json files must identify their mode, code revision and
    host/device inventory without consulting the shell history that
    produced them."""
    import multiprocessing
    import platform
    import socket

    stamp = {
        "mode": target,
        "git_sha": _git_sha(),
        "generated_at": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "host": {
            "hostname": socket.gethostname(),
            "cpus": multiprocessing.cpu_count(),
            "platform": platform.platform(),
            "python": platform.python_version(),
        },
    }
    if target == "lint":
        # graftlint is pure host AST work: no backend is touched for it
        stamp["devices"] = None
        return stamp
    try:
        import jax

        devs = jax.devices()
        stamp["devices"] = {
            "count": len(devs),
            "platform": devs[0].platform,
            "kind": getattr(devs[0], "device_kind", ""),
        }
        stamp["jax_version"] = jax.__version__
    except Exception:
        stamp["devices"] = None
    return stamp


def _phase_frac_sum(breakdown: dict) -> float:
    """Σ fractions of a span-window breakdown (the ~1.0 acceptance check)."""
    return round(
        sum(p["frac"] for p in breakdown["phases"].values()) + breakdown["other_frac"], 6
    )


def bench_dreamer_v3() -> dict:
    import numpy as np

    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.config.compose import compose
    from sheeprl_tpu.parallel.fabric import build_fabric

    size = os.environ.get("BENCH_SIZE", "S")  # smoke-test hook (e.g. XS on CPU)
    overrides = [
        "exp=dreamer_v3",
        "env=dummy",
        "env.id=discrete_dummy",
        f"algo=dreamer_v3_{size}",
        "algo.cnn_keys.encoder=[rgb]",
        "algo.mlp_keys.encoder=[]",
        "algo.per_rank_batch_size=16",
        "algo.per_rank_sequence_length=64",
        "fabric.precision=bf16-mixed",
    ]
    # BENCH_MESH='{data: 2, model: 4}': bench on a 2-D (data, model) mesh —
    # the partition-rules sharding path (docs/sharding.md); the mesh shape is
    # stamped into the JSON payload either way
    if os.environ.get("BENCH_MESH"):
        overrides.append(f"fabric.mesh_shape={os.environ['BENCH_MESH']}")
    cfg = compose(overrides)
    fabric = build_fabric(cfg)

    # Build the jitted multi-update train phase exactly as the algorithm does,
    # by reusing its inner machinery through a tiny synthetic replay block.
    L = int(os.environ.get("BENCH_L", 64))
    B = int(os.environ.get("BENCH_B", 16))
    U = int(os.environ.get("BENCH_U", 4))
    rng = np.random.default_rng(0)
    # TPU tiled layout pads the pixel block ~2x (measured: (1024,64,16,64,64,3)
    # u8 allocates 25.8 GiB for 12.9 GiB raw) — refuse shapes whose PER-DEVICE
    # share (the block shards over the mesh) cannot fit HBM next to params,
    # instead of hanging in a doomed compile.  Emitted as a JSON result, not
    # an exception.
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        hbm = (dev.memory_stats() or {}).get("bytes_limit", 16 * 2**30)
        per_dev = U * L * B * 64 * 64 * 3 * 2.2 / max(len(jax.devices()), 1)
        if per_dev > 0.9 * hbm:
            return {
                "metric": (
                    f"bench_refused: (U={U}, L={L}, B={B}) needs ~{per_dev / 2**30:.1f} GiB "
                    f"padded per device vs {hbm / 2**30:.0f} GiB HBM; reduce BENCH_U/B/L"
                ),
                "value": 0,
                "unit": "",
                "vs_baseline": None,
            }
    block = {
        "rgb": jnp.asarray(rng.integers(0, 255, (U, L, B, 64, 64, 3)).astype(np.uint8)),
        "actions": jnp.asarray(rng.integers(0, 2, (U, L, B, 4)).astype(np.float32)),
        "rewards": jnp.asarray(rng.normal(size=(U, L, B)).astype(np.float32)),
        "terminated": jnp.zeros((U, L, B), jnp.float32),
        "is_first": jnp.zeros((U, L, B), jnp.float32),
    }

    train_phase, params, opt_state = _build_dv3_train_phase(fabric, cfg)
    block = fabric.shard_batch(block, axis=2)
    key = jax.random.PRNGKey(0)

    # AOT-compile once through the compile-once layer (make_train_phase now
    # returns an AOTFunction); the SAME executable serves cost_analysis
    # (XLA's own FLOP count — no hand-derived model formula to drift), the
    # warmup and the timed loop, so the heavy train-phase program is never
    # compiled twice.  Fall back to the plain jit wrapper if AOT fails.
    # The compile-vs-steady split is reported as SEPARATE JSON fields
    # (`first_call_s` / `steady_updates_per_s`) so the trajectory can tell a
    # compile-time regression from a math-throughput one.
    # Two FLOPs-per-update estimates feed the MFU line:
    # * XLA's own cost model for the compiled executable (exact for THIS
    #   program, but per-shard under a model axis and backend-dependent);
    # * the analytic param-tree estimate (_dv3_analytic_flops) — derived
    #   from kernel shapes alone, so it is mesh-independent and always
    #   available, including on the CPU fallback where MFU still must be
    #   emitted (ISSUE 7 acceptance).
    flops_per_update = None
    flops_analytic = _dv3_analytic_flops(params, B, L, int(cfg.algo.horizon))
    t_first = time.perf_counter()
    try:
        compiled = train_phase.compile_for(params, opt_state, block, key, jnp.int32(0))
        train_phase = compiled
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        if cost and cost.get("flops"):
            # per-shard flops under a model axis: scale to the whole mesh
            flops_per_update = float(cost["flops"]) * len(fabric.devices) / U
    except Exception:
        pass  # cost analysis is best-effort; the throughput number still stands

    # warmup = first dispatch (compile happens here only on the AOT-fallback
    # path, so first_call_s covers compile + first execution either way).
    from sheeprl_tpu.utils.utils import device_sync

    params, opt_state, metrics = train_phase(params, opt_state, block, key, jnp.int32(0))
    device_sync((params, metrics))
    first_call_s = time.perf_counter() - t_first

    # Steady-state timed loop runs under jax.transfer_guard("disallow"):
    # every input is device-resident (the block was staged once, above), so
    # ANY implicit H2D inside the window raises and fails the bench — the
    # red/green spelling of the zero-copy claim (`h2d_bytes_per_update`).
    # Counters are pre-staged device scalars for the same reason.
    iters = int(os.environ.get("BENCH_ITERS", 10))
    steps_dev = [jax.device_put(np.int32(i)) for i in range(iters)]
    t0 = time.perf_counter()
    # H2D direction only: D2D resharding (multi-device meshes) is ICI, not
    # host traffic — see data/device_replay.steady_guard
    with jax.transfer_guard_host_to_device("disallow"):
        for i in range(iters):
            params, opt_state, metrics = train_phase(params, opt_state, block, key, steps_dev[i])
    device_sync((params, metrics))
    elapsed = time.perf_counter() - t0
    updates_per_s = (U * iters) / elapsed
    # The RTX-3080 baseline (0.5 updates/s) is for the S model on B=16, L=64
    # pixel batches; any overridden shape is NOT comparable — stamp the real
    # shape into the metric name and only claim vs_baseline when it matches.
    comparable = size == "S" and B == 16 and L == 64
    dev = jax.devices()[0]
    platform = dev.platform
    from sheeprl_tpu.utils.profiler import COMPILE_MONITOR

    n_exe, compile_s = COMPILE_MONITOR.totals()
    result = {
        "metric": (
            f"dreamer_v3_{size}_gradient_updates_per_s "
            f"(B={B} L={L} U={U} pixel batch, {platform})"
        ),
        "value": round(updates_per_s, 3),
        "unit": "updates/s",
        "vs_baseline": round(updates_per_s / BASELINE_DV3_UPDATES_PER_S, 3) if comparable else None,
        # compile-time vs steady-state split (compile-once layer): first_call_s
        # covers AOT lowering+compilation plus the first dispatch; the timed
        # loop above starts only after it, so `value` is pure steady-state
        "first_call_s": round(first_call_s, 3),
        "steady_updates_per_s": round(updates_per_s, 3),
        "compile_executables": n_exe,
        "compile_time_s": round(compile_s, 3),
        # utilization axis (ISSUE 7): mesh topology + FLOPs/update + MFU ride
        # in every payload so BENCH_*.json tracks utilization across rounds.
        # `mfu` uses XLA's cost model when available, `mfu_analytic` the
        # param-tree estimate; both are null (but PRESENT) on a functional
        # CPU run, which has no peak on record.
        "mesh_shape": {k: int(v) for k, v in fabric.mesh.shape.items()},
        "flops_per_update": flops_per_update,
        "flops_per_update_analytic": flops_analytic,
        "mfu": None,
        "mfu_analytic": None,
        # zero-copy dataflow axis (ISSUE 9): the timed window ran to
        # completion under jax.transfer_guard("disallow"), so the measured
        # steady state performed zero implicit H2D.  The synthetic block was
        # staged ONCE outside the window; per-update H2D is exactly 0.
        # `replay_hbm_bytes` is reported by `--mode replay`, which times the
        # fused sample+update program over a real DeviceReplay ring.
        "h2d_bytes_per_update": 0.0,
        "replay_hbm_bytes": None,
    }
    peak = _peak_flops_per_s(dev)
    if peak is not None:
        mesh_peak = peak * len(fabric.devices)
        if flops_per_update is not None:
            result["mfu"] = round(flops_per_update * updates_per_s / mesh_peak, 4)
        result["mfu_analytic"] = round(flops_analytic * updates_per_s / mesh_peak, 4)
    return result


def bench_device_replay() -> dict:
    """Zero-copy replay dataflow bench (``--mode replay``, ISSUE 9).

    Builds a real :class:`~sheeprl_tpu.data.device_replay.DeviceReplay`
    ring (DreamerV3-XS-shaped pixel data by default), appends through the
    donated-write path, then times the FUSED on-device sample+update
    program — sequence-index generation, ring gather and the full DV3 train
    phase in one AOT executable — with ``jax.transfer_guard("disallow")``
    armed over the whole steady window.  ``h2d_bytes_per_update`` is 0 by
    construction and the guard makes that a hard assertion rather than
    prose; ``replay_hbm_bytes`` reports the resident ring footprint.
    ``BENCH_REPLAY_MODE=uniform`` times the uniform-sampling gather path
    (the SAC family's dataflow) with a summing consumer instead of the
    dreamer update — isolating replay dataflow from model math.
    """
    import numpy as np

    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.config.compose import compose
    from sheeprl_tpu.data.device_replay import DeviceReplay, fused_sequence_train
    from sheeprl_tpu.parallel.fabric import build_fabric
    from sheeprl_tpu.utils.utils import device_sync, merge_framestack  # noqa: F401

    size = os.environ.get("BENCH_SIZE", "XS")
    L = int(os.environ.get("BENCH_L", 8))
    B = int(os.environ.get("BENCH_B", 4))
    U = int(os.environ.get("BENCH_U", 2))
    n_envs = int(os.environ.get("BENCH_ENVS", 4))
    window = int(os.environ.get("BENCH_REPLAY_WINDOW", 512))
    iters = int(os.environ.get("BENCH_ITERS", 10))
    mode = os.environ.get("BENCH_REPLAY_MODE", "sequence")

    cfg = compose(
        [
            "exp=dreamer_v3", "env=dummy", "env.id=discrete_dummy",
            f"algo=dreamer_v3_{size}",
            "algo.cnn_keys.encoder=[rgb]", "algo.mlp_keys.encoder=[]",
            f"algo.per_rank_batch_size={B}",
            f"algo.per_rank_sequence_length={L}",
        ]
    )
    fabric = build_fabric(cfg)
    rb = DeviceReplay(window, n_envs, mesh=fabric.mesh, data_axis=fabric.data_axis)
    rng = np.random.default_rng(0)
    # fill through the donated append path (what the actor loop does)
    chunk = 32
    for _ in range(window // chunk):
        rb.add({
            "rgb": rng.integers(0, 255, (chunk, n_envs, 64, 64, 3)).astype(np.uint8),
            "actions": rng.integers(0, 2, (chunk, n_envs, 4)).astype(np.float32),
            "rewards": rng.normal(size=(chunk, n_envs, 1)).astype(np.float32),
            "terminated": np.zeros((chunk, n_envs, 1), np.float32),
            "is_first": np.zeros((chunk, n_envs, 1), np.float32),
        })

    key = jax.random.PRNGKey(0)
    if mode == "uniform":
        def consume(p, o, batch, k, counter):
            s = sum(jnp.sum(v.astype(jnp.float32)) for v in batch.values())
            return p + 0.0 * s, o, s

        from sheeprl_tpu.data.device_replay import fused_uniform_train

        fused = fused_uniform_train(
            fabric, consume, rb, batch_size=B * L, prep=lambda b: b, name="bench.replay_uniform"
        )
        params = jax.device_put(jnp.zeros(()))
        opt_state = jax.device_put(jnp.zeros(()))
    else:
        def _prep(b):
            return {
                "rgb": b["rgb"],
                "actions": b["actions"],
                "rewards": b["rewards"][..., 0],
                "terminated": b["terminated"][..., 0],
                "is_first": b["is_first"][..., 0],
            }

        train_phase, params, opt_state = _build_dv3_train_phase(fabric, cfg)
        fused = fused_sequence_train(
            fabric, train_phase, rb, B, L, _prep, name="bench.replay_sequence"
        )

    counter = jax.device_put(np.int32(0))
    # warmup (compile) dispatch
    t_first = time.perf_counter()
    params, opt_state, counter, metrics = fused(
        params, opt_state, rb.buffers, rb.cursor, key, counter, n_samples=U
    )
    device_sync((params, metrics))
    first_call_s = time.perf_counter() - t_first

    # pre-split OUTSIDE the guard: eager `keys[i]` slicing stages its index
    # as an implicit device scalar, which the guard (correctly) rejects
    keys = list(jax.random.split(key, iters))
    # span-instrumented steady window (telemetry/spans.py): the fused
    # program is on-device sampling + update in ONE executable, so its whole
    # dispatch is the update.dispatch phase; the breakdown's fractions must
    # sum to ~1.0 (acceptance)
    from sheeprl_tpu.telemetry.spans import SPANS, span

    SPANS.roll_window()
    t0 = time.perf_counter()
    with jax.transfer_guard_host_to_device("disallow"):
        for i in range(iters):
            with span("update.dispatch"):
                params, opt_state, counter, metrics = fused(
                    params, opt_state, rb.buffers, rb.cursor, keys[i], counter, n_samples=U
                )
    device_sync((params, metrics))
    elapsed = time.perf_counter() - t0
    phase_breakdown = SPANS.breakdown()

    dev = jax.devices()[0]
    return {
        "metric": (
            f"device_replay_{mode}_updates_per_s "
            f"(dv3_{size} B={B} L={L} U={U} window={window}x{n_envs}, {dev.platform})"
        ),
        "value": round(U * iters / elapsed, 3),
        "unit": "updates/s",
        "vs_baseline": None,
        "first_call_s": round(first_call_s, 3),
        "steady_updates_per_s": round(U * iters / elapsed, 3),
        # the guard completing IS the measurement: zero implicit H2D in the
        # steady window, so per-update H2D bytes are exactly 0
        "h2d_bytes_per_update": 0.0,
        "replay_hbm_bytes": rb.hbm_bytes,
        "mesh_shape": {k: int(v) for k, v in fabric.mesh.shape.items()},
        "phase_breakdown": phase_breakdown,
        "phase_frac_sum": _phase_frac_sum(phase_breakdown),
    }


def _dv3_analytic_flops(params, batch: int, seq_len: int, horizon: int) -> float:
    """Analytic FLOPs per gradient update from the param tree alone.

    Purpose: a mesh- and backend-independent MFU denominator that cannot
    silently change when the compiled program does (the 8.8% -> >=25% claim
    must be measured against a fixed cost model).  It is an independent
    cross-check of XLA's per-executable count, not a replica of it: XLA
    sees the post-optimization HLO (and its CPU cost model is known to
    count convolutions differently), so the two can differ by ~2x on tiny
    presets — `mfu` (XLA) is primary when the backend provides it,
    `mfu_analytic` is the always-available, never-silently-changing one.

    Cost model (per token, fwd = 2*prod(kernel) MACs; train = 3x fwd for
    forward + both backward matmuls):

    * world-model phase (encoder, RSSM scan, decoder, reward/continue
      heads): every kernel trains on B*L sequence tokens;
    * imagination phase: the RSSM dynamics (recurrent+transition) and the
      actor roll `horizon` steps from B*L start states — the dynamics are
      forward-only under DreamerV3's straight-through/REINFORCE estimator
      (1x), the actor trains (3x);
    * critic + target critic evaluate horizon+1 imagined states: critic
      trains (3x), the EMA target is forward-only (1x).

    Conv/deconv kernels are weighted by their spatial position count in the
    64x64 stride-2 pyramid (conv_i at (32/2^i)^2 positions, deconv_i
    mirrored, the final RGB deconv at 64^2); dense kernels count once per
    token.
    """
    import re as _re

    import jax
    import numpy as _np
    from jax.tree_util import tree_flatten_with_path

    def kernel_fwd_flops(tree) -> float:
        flat, _ = tree_flatten_with_path(tree)
        total = 0.0
        for kp, leaf in flat:
            if getattr(leaf, "ndim", 0) < 2:
                continue
            path = "/".join(str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k)))) for k in kp)
            macs = float(_np.prod(leaf.shape))
            m = _re.search(r"(de)?conv_(\d+)|deconv_out", path)
            if m and leaf.ndim == 4:
                if "deconv_out" in path:
                    positions = 64 * 64
                elif m.group(1):  # deconv_i: 4x4 latent grid upsampled 2x per layer
                    positions = (4 * 2 ** (int(m.group(2)) + 1)) ** 2
                else:  # conv_i: 64x64 downsampled 2x per layer
                    positions = (64 // 2 ** (int(m.group(2)) + 1)) ** 2
                macs *= positions
            total += 2.0 * macs
        return total

    p = params if isinstance(params, dict) else jax.device_get(params)
    tokens = float(batch * seq_len)
    wm = kernel_fwd_flops(p.get("world_model", {}))
    actor = kernel_fwd_flops(p.get("actor", {}))
    critic = kernel_fwd_flops(p.get("critic", {}))
    target = kernel_fwd_flops(p.get("target_critic", {}))
    dyn = kernel_fwd_flops(
        {
            k: v
            for k, v in (p.get("world_model", {}).get("params", {}) or {}).items()
            if k in ("recurrent_model", "transition_model")
        }
    )
    return (
        3.0 * tokens * wm
        + tokens * horizon * (dyn + 3.0 * actor)
        + tokens * (horizon + 1) * (3.0 * critic + target)
    )


#: Peak bf16 FLOP/s per chip, keyed by the exact ``device_kind`` JAX reports.
#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB
#: HBM at 819 GB/s).  A device that is not here is an error, not a default.
PEAK_BF16_FLOPS_PER_S = {
    "TPU v5 lite": 197e12,
}


def _peak_flops_per_s(dev) -> float | None:
    """Peak bf16 FLOP/s of ``dev`` from :data:`PEAK_BF16_FLOPS_PER_S`; None
    on the CPU (a functional run reports no MFU); any other device the table
    does not know raises — MFU is never printed against a guessed peak."""
    if dev.platform == "cpu":
        return None
    kind = getattr(dev, "device_kind", "")
    if kind not in PEAK_BF16_FLOPS_PER_S:
        raise KeyError(
            f"no peak FLOP/s on record for device_kind {kind!r}; add it to "
            "PEAK_BF16_FLOPS_PER_S with its source"
        )
    return PEAK_BF16_FLOPS_PER_S[kind]


def _build_dv3_train_phase(fabric, cfg):
    """Construct DreamerV3 modules + the single-dispatch train phase the
    training script uses, against a synthetic Dict observation space."""
    import numpy as np
    from gymnasium import spaces

    import jax

    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as dv3

    obs_space = spaces.Dict({"rgb": spaces.Box(0, 255, (64, 64, 3), np.uint8)})

    # reuse the module-level pieces by instantiating a miniature "main"
    # closure: we inline the same construction path
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import build_dv3_optimizers

    world_model, actor, critic, params = build_agent(fabric, (4,), False, cfg, obs_space)
    wm_opt, actor_opt, critic_opt, opt_state = build_dv3_optimizers(fabric, cfg, params)
    # params/opt_state pin the partition-rules state shardings on the program
    # exactly as the training loop does — the benchmarked program IS the
    # training program, mesh topology included
    train_phase = dv3.make_train_phase(
        fabric, cfg, world_model, actor, critic, wm_opt, actor_opt, critic_opt,
        cnn_keys=("rgb",), mlp_keys=(), is_continuous=False,
        params=params, opt_state=opt_state,
    )
    return train_phase, params, opt_state


def bench_cpu_wall_clock(algo: str) -> dict:
    """Run the EXACT reference benchmark workload (exp=<algo>_benchmarks —
    same env, env count, rollout/batch shapes and step budget as the
    reference's published run, logging and test disabled) end-to-end and
    report wall-clock vs the reference's published 4-CPU number
    (/root/reference/README.md:83-189: 65536 steps for ppo/a2c/sac, 16384
    for the tiny-model dreamer rows)."""
    import multiprocessing

    from sheeprl_tpu.cli import run
    from sheeprl_tpu.config.compose import compose

    # BENCH_ARGS: extra CLI overrides, stamped into the metric name so a
    # modified workload can never masquerade as the reference one
    extra = os.environ.get("BENCH_ARGS", "").split()
    exp = algo.removesuffix("_wall")
    args = [
        f"exp={exp}_benchmarks",
        "print_config=False",
        "log_dir=/tmp/bench_logs",
        *extra,
    ]
    # the step count comes from the composed workload itself, never a
    # hardcoded constant that could drift from the exp config
    steps = int(compose(args).algo.total_steps)
    t0 = time.perf_counter()
    run(args)
    elapsed = time.perf_counter() - t0
    ncpu = multiprocessing.cpu_count()
    label = f" [{' '.join(extra)}]" if extra else ""
    if os.environ.get("BENCH_ON_ACCEL"):
        import jax

        host = f"1x {jax.devices()[0].device_kind} vs 4-CPU baseline"
    else:
        host = f"{ncpu}-core host vs 4-CPU baseline"
    return {
        "metric": f"{exp}_benchmarks_{steps}_steps_wall_clock ({host}){label}",
        "value": round(elapsed, 2),
        "unit": "s",
        # vs_baseline only for the untouched reference workload — a modified
        # one gets the bracketed label and no numeric comparison
        "vs_baseline": round(BASELINE_CPU_WALL_CLOCK_S[algo] / elapsed, 3) if not extra else None,
    }


def _tiny_serve_ckpt(algo: str, prefix: str = "bench_serve_") -> str:
    """A committed tiny-dryrun checkpoint to serve from (shared by the
    ``serve`` and ``serve_fleet`` benches)."""
    import tempfile

    from sheeprl_tpu.cli import run
    from tests.ckpt_utils import find_checkpoints

    log_dir = tempfile.mkdtemp(prefix=prefix)
    env_id = "continuous_dummy" if algo.startswith("sac") else "discrete_dummy"
    args = [
        f"exp={algo}", "env=dummy", f"env.id={env_id}", "dry_run=True",
        "env.num_envs=2", "env.sync_env=True", "env.capture_video=False",
        "fabric.devices=1", "metric.log_level=0", "checkpoint.every=1",
        "buffer.memmap=False", "algo.learning_starts=0",
        f"log_dir={log_dir}", "print_config=False", "algo.run_test=False",
    ]
    if algo == "dreamer_v3":
        args += [
            "algo=dreamer_v3_XS", "algo.per_rank_batch_size=2",
            "algo.per_rank_sequence_length=8", "algo.horizon=4",
            "algo.cnn_keys.encoder=[rgb]", "algo.mlp_keys.encoder=[state]",
            "algo.world_model.encoder.cnn_channels_multiplier=4",
            "algo.dense_units=16",
            "algo.world_model.recurrent_model.recurrent_state_size=16",
            "algo.world_model.transition_model.hidden_size=16",
            "algo.world_model.representation_model.hidden_size=16",
        ]
    run(args)
    return str(find_checkpoints(log_dir)[-1])


def bench_serve() -> dict:
    """Policy-as-a-service load benchmark (``--mode serve``).

    Stands up a :class:`~sheeprl_tpu.serve.service.PolicyService` on a
    committed checkpoint (``BENCH_SERVE_CKPT``, or a fresh tiny dryrun of
    ``BENCH_SERVE_ALGO``, default ppo), then ``BENCH_SERVE_CLIENTS``
    threads each stream ``BENCH_SERVE_REQUESTS`` blocking act() calls
    through the continuous batcher.  Reports steady-state **actions/s**
    plus the latency percentiles (p50/p99 ms) and the compile counters —
    ``steady_compiles`` must be 0: the batch ladder is AOT-warmed before
    the timed window, so a nonzero value means a shape escaped the ladder.
    """
    import threading

    import numpy as np

    algo = os.environ.get("BENCH_SERVE_ALGO", "ppo")
    ckpt = os.environ.get("BENCH_SERVE_CKPT") or _tiny_serve_ckpt(algo)

    from sheeprl_tpu.serve import PolicyService
    from sheeprl_tpu.utils.profiler import COMPILE_MONITOR

    clients = int(os.environ.get("BENCH_SERVE_CLIENTS", 16))
    per_client = int(os.environ.get("BENCH_SERVE_REQUESTS", 64))
    service = PolicyService.from_checkpoint(ckpt, ["serve.watch_commits=False"])
    service.start()  # warms the whole batch ladder before returning
    obs = {
        k: np.zeros(shape, np.dtype(dt))
        for k, (shape, dt) in service.player.obs_spec.items()
    }
    # settle the pipeline outside the timed window (first dispatches mix in
    # host-side warmup noise), then snapshot the compile counter: any compile
    # during the timed window is a ladder escape
    for _ in range(4):
        service.act(obs, timeout=60.0)
    exe_before, _ = COMPILE_MONITOR.totals()
    service.latency = type(service.latency)(int(clients * per_client * 1.1))

    barrier = threading.Barrier(clients + 1)
    errors: list = []

    def worker(wid: int) -> None:
        barrier.wait()
        for _ in range(per_client):
            try:
                service.act(obs, session=f"bench-{wid}", timeout=120.0)
            except Exception as e:  # count, don't crash the bench
                errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(clients)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    exe_after, compile_s = COMPILE_MONITOR.totals()
    total = clients * per_client - len(errors)
    stats = service.stats()
    service.stop()
    import jax

    return {
        "metric": (
            f"serve_{algo}_actions_per_s "
            f"({clients} clients x {per_client} reqs, "
            f"ladder {stats['batch_ladder']}, {jax.devices()[0].platform})"
        ),
        "value": round(total / elapsed, 3),
        "unit": "actions/s",
        "vs_baseline": None,
        "p50_ms": round(stats["p50_ms"], 3),
        "p99_ms": round(stats["p99_ms"], 3),
        "avg_batch": stats["avg_batch"],
        "padded_frac": stats["padded_frac"],
        "serve_errors": len(errors),
        "steady_compiles": exe_after - exe_before,
        "compile_executables": exe_after,
        "compile_time_s": round(compile_s, 3),
    }


def bench_serve_fleet() -> dict:
    """Fault-tolerant serving-fleet benchmark (``--mode serve_fleet``,
    ISSUE 17).

    Three phases over REAL replica processes (``LocalFleet`` spawning
    ``python -m sheeprl_tpu.serve``) behind a ``FleetRouter`` front:

    * **A (baseline)** — a 1-replica fleet under ``BENCH_FLEET_CLIENTS``
      threads x ``BENCH_FLEET_REQUESTS`` acts: the router-included
      single-replica actions/s;
    * **B (scaling)** — the same load over ``BENCH_FLEET_REPLICAS``
      replicas; per-replica efficiency = thr_R / (thr_1 * R) must reach
      ``BENCH_FLEET_SCALE_FLOOR`` (default 0.8);
    * **C (chaos)** — the same fleet with one replica SIGKILLed
      mid-window: zero dropped requests, every session completes.

    ``gate_failed`` on any drop, any lost session, or sub-floor scaling.
    """
    import signal
    import threading

    import numpy as np

    from sheeprl_tpu.serve.client import PolicyClient
    from sheeprl_tpu.serve.fleet import FleetRouter, FleetServer, LocalFleet

    algo = os.environ.get("BENCH_SERVE_ALGO", "ppo")
    ckpt = os.environ.get("BENCH_SERVE_CKPT") or _tiny_serve_ckpt(algo, "bench_fleet_")
    replicas = max(2, int(os.environ.get("BENCH_FLEET_REPLICAS", 2)))
    clients = int(os.environ.get("BENCH_FLEET_CLIENTS", 16))
    per_client = int(os.environ.get("BENCH_FLEET_REQUESTS", 64))
    floor = float(os.environ.get("BENCH_FLEET_SCALE_FLOOR", 0.8))
    cfg = {"serve": {"fleet": {"health_poll_s": 0.2, "eject_threshold": 2, "readmit_s": 0.5}}}
    overrides = ["serve.batch_ladder=[1,8,16]", "serve.max_wait_ms=2"]

    def run_load(url: str, kill_after_s: float = -1.0, fleet=None, sessions=False):
        """(elapsed_s, completed_sessions, errors) for one client storm.

        Scaling phases run sessionless (least-loaded dispatch spreads the
        load evenly); the chaos phase runs session-bearing so the kill also
        exercises sticky re-routing and session completion."""
        barrier = threading.Barrier(clients + 1)
        done: list = []
        errors: list = []

        def worker(wid: int) -> None:
            client = PolicyClient(url, timeout=120.0, retries=8, retry_base_s=0.2)
            session = f"bench-{wid}" if sessions else None
            barrier.wait(timeout=300.0)
            try:
                for _ in range(per_client):
                    client.act(obs, greedy=True, session=session)
                done.append(wid)
            except Exception as e:  # the gate IS "no exception"
                errors.append(repr(e))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(clients)]
        for t in threads:
            t.start()
        barrier.wait(timeout=300.0)
        t0 = time.perf_counter()
        if kill_after_s >= 0:
            killer = threading.Timer(
                kill_after_s, lambda: fleet.kill(0, sig=signal.SIGKILL)
            )
            killer.start()
        for t in threads:
            t.join(600.0)
        elapsed = time.perf_counter() - t0
        return elapsed, len(done), errors

    results: dict = {}
    total = clients * per_client
    for phase, n in (("single", 1), ("fleet", replicas)):
        fleet = LocalFleet(
            ckpt, overrides=overrides, replicas=n,
            backoff_base_s=0.2, backoff_max_s=1.0, echo=False,
        )
        fleet.start()
        server = None
        try:
            router = FleetRouter(fleet.addresses(), cfg)
            fleet.attach(router)
            server = FleetServer(router)
            server.start()
            if not router.wait_healthy(min_replicas=n, timeout=300.0):
                raise RuntimeError(f"{phase}: fleet never became healthy: {router.health()}")
            health = PolicyClient(server.url, timeout=120.0).health()
            obs = {
                k: np.zeros(shape, np.dtype(dt))
                for k, (shape, dt) in health["obs_spec"].items()
            }
            run_load(server.url)  # settle: warm every replica + HTTP path
            elapsed, completed, errors = run_load(server.url)
            results[phase] = {
                "actions_per_s": round(total / elapsed, 3),
                "elapsed_s": round(elapsed, 3),
                "completed_sessions": completed,
                "dropped": len(errors),
                "errors": errors[:3],
            }
            if phase == "fleet":
                # phase C on the same fleet: kill a replica mid-window
                elapsed, completed, errors = run_load(
                    server.url,
                    kill_after_s=max(0.3, elapsed / 4),
                    fleet=fleet,
                    sessions=True,
                )
                stats = router.stats()
                results["chaos"] = {
                    "actions_per_s": round(total / elapsed, 3),
                    "completed_sessions": completed,
                    "dropped": len(errors),
                    "errors": errors[:3],
                    "failovers": stats["failovers"],
                    "ejects": stats["ejects"],
                    "respawns": stats["respawns"],
                }
        finally:
            if server is not None:
                server.stop()
            fleet.stop()

    thr_1 = results["single"]["actions_per_s"]
    thr_r = results["fleet"]["actions_per_s"]
    efficiency = thr_r / (thr_1 * replicas) if thr_1 > 0 else 0.0
    dropped = sum(results[p]["dropped"] for p in results)
    lost_sessions = sum(clients - results[p]["completed_sessions"] for p in results)
    # the scaling gate needs a host that can actually back R replica
    # processes plus the router: on fewer cores linear scaling is
    # physically impossible, so efficiency is reported but not gated
    cores = os.cpu_count() or 1
    scale_gated = cores >= replicas + 1
    gate_failed = (
        dropped > 0 or lost_sessions > 0 or (scale_gated and efficiency < floor)
    )
    label = "" if scale_gated else f" [scaling ungated: {cores} cpus for {replicas} replicas]"
    return {
        "metric": (
            f"serve_fleet_{algo}_actions_per_s "
            f"({replicas} replicas, {clients} clients x {per_client} reqs, "
            f"SIGKILL chaos phase){label}"
        ),
        "value": thr_r,
        "unit": "actions/s",
        "vs_baseline": None,
        "single_replica_actions_per_s": thr_1,
        "scaling_efficiency_per_replica": round(efficiency, 3),
        "scale_floor": floor,
        "scale_gated": scale_gated,
        "dropped_requests": dropped,
        "lost_sessions": lost_sessions,
        "phases": results,
        "gate_failed": gate_failed,
    }


def bench_env() -> dict:
    """Env-stepping throughput axis (``--mode env`` / ``BENCH_TARGET=env``,
    ISSUE 11): env-steps/s for the three rollout dataflows on CartPole-class
    dynamics —

    * ``cpu_gym_async`` — gymnasium ``AsyncVectorEnv`` over CPU gym
      processes (the historical path);
    * ``jax_adapter`` — the same pure-JAX env stepped one jitted program
      per step through ``JaxToGymAdapter`` + ``SyncVectorEnv`` (the
      compatibility path every algo can use);
    * ``jax_fused`` — the Anakin dataflow: ONE jitted ``lax.scan`` over the
      batched in-trace env step (``VectorJaxEnv``), thousands of instances
      per dispatch, zero host round-trips.

    Actions are pre-sampled/constant so the axis isolates env dataflow from
    policy math.  The fused number uses many more instances on purpose —
    batch scale IS the Anakin win; per-path env counts are reported.
    """
    import numpy as np

    import gymnasium as gym
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.envs.jax.adapter import JaxToGymAdapter
    from sheeprl_tpu.envs.jax.core import VectorJaxEnv
    from sheeprl_tpu.envs.jax.registry import make_jax_env

    n_async = int(os.environ.get("BENCH_ENVS", 16))
    n_fused = int(os.environ.get("BENCH_FUSED_ENVS", 1024))
    steps = int(os.environ.get("BENCH_ENV_STEPS", 512))
    fused_iters = int(os.environ.get("BENCH_ENV_ITERS", 8))

    rng = np.random.default_rng(0)
    actions = rng.integers(0, 2, (steps, n_async)).astype(np.int64)

    # ---- cpu gym async (the AsyncVectorEnv baseline) ----------------------
    venv = gym.vector.AsyncVectorEnv(
        [lambda: gym.make("CartPole-v1") for _ in range(n_async)]
    )
    venv.reset(seed=0)
    # one warm step outside the timer (worker spin-up)
    venv.step(actions[0])
    t0 = time.perf_counter()
    for i in range(steps):
        venv.step(actions[i])
    cpu_gym_rate = steps * n_async / (time.perf_counter() - t0)
    venv.close()

    # ---- jax adapter through SyncVectorEnv (its shipped path) -------------
    senv = gym.vector.SyncVectorEnv(
        [lambda: JaxToGymAdapter(make_jax_env("cartpole")) for _ in range(n_async)]
    )
    senv.reset(seed=0)
    senv.step(actions[0])
    t0 = time.perf_counter()
    for i in range(steps):
        senv.step(actions[i])
    adapter_rate = steps * n_async / (time.perf_counter() - t0)
    senv.close()

    # ---- Anakin fused scan -------------------------------------------------
    fused_env = VectorJaxEnv(make_jax_env("cartpole"), n_fused)

    def fused_rollout(state, key):
        def body(carry, k):
            state = carry
            acts = jax.random.bernoulli(k, shape=(n_fused,)).astype(jnp.int32)
            state, _, reward, term, trunc, _ = fused_env.step(state, acts)
            return state, reward

        state, rewards = jax.lax.scan(body, state, jax.random.split(key, steps))
        return state, jnp.sum(rewards)

    fused_rollout = jax.jit(fused_rollout, donate_argnums=(0,))
    state, _ = fused_env.reset(jax.random.PRNGKey(0))
    t_first = time.perf_counter()
    state, s = fused_rollout(state, jax.random.PRNGKey(1))
    s.block_until_ready()
    first_call_s = time.perf_counter() - t_first
    keys = list(jax.random.split(jax.random.PRNGKey(2), fused_iters))
    from sheeprl_tpu.telemetry.spans import SPANS, span

    SPANS.roll_window()
    t0 = time.perf_counter()
    with jax.transfer_guard_host_to_device("disallow"):
        for i in range(fused_iters):
            with span("rollout"):
                state, s = fused_rollout(state, keys[i])
    s.block_until_ready()
    fused_rate = steps * n_fused * fused_iters / (time.perf_counter() - t0)
    phase_breakdown = SPANS.breakdown()

    dev = jax.devices()[0]
    return {
        "metric": (
            f"env_steps_per_s (cartpole: cpu-gym async x{n_async} vs jax adapter "
            f"x{n_async} vs jax fused x{n_fused}, {dev.platform})"
        ),
        "value": round(fused_rate, 1),
        "unit": "env_steps/s",
        # the acceptance comparison: fused Anakin rollout vs the
        # AsyncVectorEnv cpu-gym baseline on this host
        "vs_baseline": round(fused_rate / cpu_gym_rate, 2),
        "env_steps_per_s_cpu_gym_async": round(cpu_gym_rate, 1),
        "env_steps_per_s_jax_adapter": round(adapter_rate, 1),
        "env_steps_per_s_jax_fused": round(fused_rate, 1),
        "n_envs_async": n_async,
        "n_envs_fused": n_fused,
        "first_call_s": round(first_call_s, 3),
        # guard completion == zero H2D inside the fused steady loop
        "h2d_bytes_per_update": 0.0,
        "phase_breakdown": phase_breakdown,
        "phase_frac_sum": _phase_frac_sum(phase_breakdown),
    }


def bench_population() -> dict:
    """Population-axis scaling bench (``--mode population`` /
    ``BENCH_TARGET=population``, ISSUE 20): per-member env-steps/s of a
    population=P CartPole phase — rollout + policy-gradient update + the
    in-trace PBT exploit/explore gate, vmapped over P members inside ONE
    donated-carry fused executable — against the SAME member phase compiled
    single-agent.

    ``per_member_scaling = (pop_rate / P) / single_rate``: the fraction of
    a lone agent's throughput each population member retains.  GATES the
    ISSUE 20 acceptance: ``per_member_scaling >= 0.7 x hardware-ideal`` at
    P=4 (training 4 members together must cost well under 4 sequential
    runs — the batched population is the point) and ``steady_compiles ==
    0`` with both executables at ``cache_size() == 1`` under the armed
    transfer guard (``h2d_bytes_per_update == 0`` by guard completion).

    The hardware-ideal term keeps the gate honest across hosts: on an
    accelerator (or any host with >= P cores) ideal is 1.0 and the gate is
    the plain ``>= 0.7``; on an N-core CPU host with N < P the members'
    compute genuinely serializes, so ideal degrades to ``N / P`` — the
    gate then measures the vmap/PBT machinery's *overhead* rather than
    penalizing the host for lacking parallel compute units.
    """
    import numpy as np

    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.algos.ppo.agent import sample_actions
    from sheeprl_tpu.envs.jax.anakin import make_rollout_fn
    from sheeprl_tpu.envs.jax.cartpole import JaxCartPole
    from sheeprl_tpu.envs.jax.core import VectorJaxEnv
    from sheeprl_tpu.parallel.fabric import Fabric
    from sheeprl_tpu.population import (
        PBTConfig,
        init_population_state,
        make_population_phase,
        tile_stack,
    )
    from sheeprl_tpu.utils.profiler import COMPILE_MONITOR
    from sheeprl_tpu.utils.structured import dotdict
    from sheeprl_tpu.utils.utils import device_sync

    pop_size = int(os.environ.get("BENCH_POP_SIZE", 4))
    num_envs = int(os.environ.get("BENCH_POP_ENVS", 64))
    rollout_steps = int(os.environ.get("BENCH_POP_ROLLOUT", 32))
    iters = int(os.environ.get("BENCH_POP_ITERS", 16))

    fabric = Fabric(devices=1)
    venv = VectorJaxEnv(JaxCartPole(), num_envs)

    def apply(p, obs):
        h = jnp.tanh(obs["state"] @ p["w1"]) @ p["w2"]
        return h[..., :2], h[..., 2:3]

    rollout_fn = make_rollout_fn(
        venv,
        apply,
        lambda out, k: sample_actions(out, (2,), False, k),
        cnn_keys=(),
        mlp_keys=("state",),
        action_space=venv.single_action_space,
        gamma=0.99,
        rollout_steps=rollout_steps,
    )

    def pg_loss(p, traj):
        # one-step PG surrogate + value regression: a real gradient through
        # the policy net, small enough that env stepping stays the axis
        logits, value = apply(p, traj)
        logp = jax.nn.log_softmax(logits)
        act = traj["actions"][..., 0].astype(jnp.int32)
        chosen = jnp.take_along_axis(logp, act[..., None], axis=-1)[..., 0]
        adv = traj["rewards"] - jax.lax.stop_gradient(value[..., 0])
        return (-chosen * adv).mean() + 0.5 * ((value[..., 0] - traj["rewards"]) ** 2).mean()

    def member_phase(p, o_state, actor, k, hp):
        actor, traj, last_obs, stats = rollout_fn(p, actor, k)
        grads = jax.grad(pg_loss)(p, traj)
        p = jax.tree.map(lambda w, g: w - hp["lr"] * g, p, grads)
        o_state = jax.tree.map(lambda m, g: 0.9 * m + g, o_state, grads)
        return p, o_state, actor, (jnp.zeros(()),), stats

    def init_params(key):
        k1, k2 = jax.random.split(key)
        return {
            "w1": 0.1 * jax.random.normal(k1, (4, 32), jnp.float32),
            "w2": 0.1 * jax.random.normal(k2, (32, 3), jnp.float32),
        }

    def init_actor(key):
        env_state, _ = venv.reset(key)
        return {
            "env": env_state,
            "ep_ret": jnp.zeros((num_envs,), jnp.float32),
            "ep_len": jnp.zeros((num_envs,), jnp.int32),
            "update": jnp.zeros((), jnp.int32),
        }

    pbt_cfg = PBTConfig.from_cfg(
        dotdict(
            {
                "population": dict(
                    size=pop_size, exploit_every=5, warmup=2, frac=0.25,
                    perturb_min=0.8, perturb_max=1.25, init_min=0.5,
                    init_max=2.0, bound_min=0.05, bound_max=20.0,
                    fitness_alpha=0.3, levels=None,
                )
            }
        ),
        base={"lr": 1e-2},
    )

    def _measure(step_fn, args, env_steps_per_iter, keep=None):
        # `keep`: how many leading outputs feed back as the next call's args
        # (the population phase also returns losses/stats, which don't)
        t0 = time.perf_counter()
        args = step_fn(*args)[:keep]
        device_sync(args)
        first_call_s = time.perf_counter() - t0
        n0, _ = COMPILE_MONITOR.totals()
        t0 = time.perf_counter()
        with jax.transfer_guard_host_to_device("disallow"):
            for _ in range(iters):
                args = step_fn(*args)[:keep]
        device_sync(args)
        wall = time.perf_counter() - t0
        n1, _ = COMPILE_MONITOR.totals()
        return {
            "rate": env_steps_per_iter * iters / wall,
            "first_call_s": first_call_s,
            "steady_compiles": n1 - n0,
            "cache_size": step_fn.cache_size(),
        }

    # ---- single-agent Anakin arm (fixed hyperparams baked in) -------------
    single_hp = {"lr": jnp.float32(1e-2)}

    def single_fused(p, o_state, actor, k):
        k, k_m = jax.random.split(k)
        p, o_state, actor, _, _ = member_phase(p, o_state, actor, k_m, single_hp)
        return p, o_state, actor, k

    single_step = fabric.compile(
        single_fused, name="bench.population.single", donate_argnums=(0, 1, 2)
    )
    params1 = fabric.replicate(init_params(jax.random.PRNGKey(0)))
    opt1 = jax.tree.map(jnp.zeros_like, params1)
    single = _measure(
        single_step,
        (params1, opt1, init_actor(jax.random.PRNGKey(1)), jax.random.PRNGKey(2)),
        num_envs * rollout_steps,
    )

    # ---- population arm (P members + in-trace PBT, one executable) --------
    population_step = fabric.compile(
        make_population_phase(member_phase, pbt_cfg),
        name="bench.population.phase",
        donate_argnums=(0, 1, 2, 3),
    )
    params = jax.vmap(init_params)(jax.random.split(jax.random.PRNGKey(0), pop_size))
    opt = jax.tree.map(jnp.zeros_like, params)
    members = jax.vmap(init_actor)(jax.random.split(jax.random.PRNGKey(1), pop_size))
    pop_state = init_population_state(members, pbt_cfg, num_envs)
    hp = pbt_cfg.init_hyperparams(jax.random.PRNGKey(3))
    pop = _measure(
        population_step,
        (params, opt, pop_state, hp, jax.random.PRNGKey(4)),
        pop_size * num_envs * rollout_steps,
        keep=5,
    )

    per_member_rate = pop["rate"] / pop_size
    scaling = per_member_rate / single["rate"]
    steady_compiles = single["steady_compiles"] + pop["steady_compiles"]
    cache_ok = single["cache_size"] == 1 and pop["cache_size"] == 1
    dev = jax.devices()[0]
    ideal = min(1.0, (os.cpu_count() or 1) / pop_size) if dev.platform == "cpu" else 1.0
    scaling_floor = 0.7 * ideal
    return {
        "metric": (
            f"per_member_env_steps_per_s (cartpole pop={pop_size} x{num_envs} envs "
            f"vs single-agent anakin, {dev.platform})"
        ),
        "value": round(per_member_rate, 1),
        "unit": "env_steps/s",
        "per_member_scaling": round(scaling, 3),
        "per_member_scaling_floor": round(scaling_floor, 3),
        "env_steps_per_s_single": round(single["rate"], 1),
        "env_steps_per_s_population_total": round(pop["rate"], 1),
        "population_size": pop_size,
        "n_envs_per_member": num_envs,
        "first_call_s_single": round(single["first_call_s"], 3),
        "first_call_s_population": round(pop["first_call_s"], 3),
        "steady_compiles": steady_compiles,
        "cache_size_single": single["cache_size"],
        "cache_size_population": pop["cache_size"],
        # guard completion over every steady window == zero H2D
        "h2d_bytes_per_update": 0.0,
        "gate_failed": not (scaling >= scaling_floor and steady_compiles == 0 and cache_ok),
    }


def bench_sebulba() -> dict:
    """Sebulba actor–learner topology bench (``--mode sebulba``, ISSUE 12).

    Two measured runs of decoupled PPO on jax CartPole:

    * **adapter-path decoupled baseline** — the pipelined single-controller
      ``ppo_decoupled`` stepping the jax env through ``JaxToGymAdapter``
      (the pre-Sebulba dataflow);
    * **sebulba** — the device-group split (``topology=sebulba``): fused
      jax-env rollout shards on the actor devices, the learner sub-mesh
      consuming the device-resident trajectory queue, learner→actor D2D
      param broadcast, transfer guard ARMED over post-warmup actor windows.

    Reports env_steps/s + learner updates/s + actor_idle_frac +
    queue_depth_frac + staleness, and GATES the ISSUE 12 acceptance:
    every actor executable holds ``cache_size() == 1`` across the
    ``BENCH_SEBULBA_UPDATES`` (default 50) steady windows, and the
    sebulba run beats the adapter-path baseline on env-steps/s.
    """
    # CPU hosts need fake devices for a real device split — must land in
    # XLA_FLAGS before the backend initializes (no-op if already forced)
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()

    import jax

    from sheeprl_tpu.config.compose import compose
    from sheeprl_tpu.parallel.fabric import build_fabric
    from sheeprl_tpu.sebulba.ppo import run_sebulba

    n_devices = len(jax.devices())
    n_actors = int(os.environ.get("BENCH_SEBULBA_ACTORS", max(1, n_devices // 2)))
    n_envs = int(os.environ.get("BENCH_SEBULBA_ENVS", 16))
    rollout_steps = int(os.environ.get("BENCH_SEBULBA_T", 16))
    updates = int(os.environ.get("BENCH_SEBULBA_UPDATES", 50))
    baseline_updates = int(os.environ.get("BENCH_SEBULBA_BASELINE_UPDATES", 8))

    common = [
        "exp=ppo_decoupled",
        "env=jax_cartpole",
        f"env.num_envs={n_envs}",
        "env.capture_video=False",
        "fabric.accelerator=auto",
        f"fabric.devices={n_devices}",
        f"algo.rollout_steps={rollout_steps}",
        f"algo.per_rank_batch_size={n_envs * rollout_steps}",
        "algo.update_epochs=1",
        "algo.cnn_keys.encoder=[]",
        "algo.mlp_keys.encoder=[state]",
        "algo.max_recompiles=1",
        "algo.run_test=False",
        "checkpoint.every=0",
        "checkpoint.save_last=False",
        "buffer.memmap=False",
        "metric.log_level=0",
        "print_config=False",
    ]

    # ---- adapter-path decoupled baseline (pipelined topology) -------------
    from sheeprl_tpu.algos.ppo.ppo_decoupled import main as ppo_decoupled_main

    base_steps = n_envs * rollout_steps * baseline_updates
    cfg = compose(common + [
        f"algo.total_steps={base_steps}",
        "log_dir=/tmp/bench_sebulba_baseline",
    ])
    fabric = build_fabric(cfg)
    t0 = time.perf_counter()
    ppo_decoupled_main(fabric, cfg)
    baseline_wall = time.perf_counter() - t0
    baseline_rate = base_steps / baseline_wall

    # ---- sebulba device split ---------------------------------------------
    seb_steps = n_envs * rollout_steps * updates
    cfg = compose(common + [
        "topology=sebulba",
        f"topology.actor_devices={n_actors}",
        f"algo.total_steps={seb_steps}",
        "buffer.transfer_guard=True",  # actor steady windows run guarded
        "log_dir=/tmp/bench_sebulba_run",
    ])
    fabric = build_fabric(cfg)
    stats = run_sebulba(fabric, cfg)

    cache_ok = all(
        all(size == 1 for size in sizes.values()) for sizes in stats["actor_cache_sizes"]
    )
    beats = stats["env_steps_per_s"] > baseline_rate
    dev = jax.devices()[0]
    return {
        "metric": (
            f"sebulba_env_steps_per_s (ppo_decoupled jax-cartpole x{n_envs}, "
            f"{n_actors} actor + {max(n_devices - n_actors, 1)} learner devices, "
            f"{updates} windows, {dev.platform})"
        ),
        "value": round(stats["env_steps_per_s"], 1),
        "unit": "env_steps/s",
        # the acceptance comparison: sebulba jax-env actors vs the
        # adapter-path pipelined decoupled baseline on this host
        "vs_baseline": round(stats["env_steps_per_s"] / baseline_rate, 2),
        "env_steps_per_s": round(stats["env_steps_per_s"], 1),
        "env_steps_per_s_adapter_baseline": round(baseline_rate, 1),
        "updates_per_s": round(stats["updates_per_s"], 3),
        "actor_idle_frac": round(stats["actor_idle_frac"], 4),
        "queue_depth_frac": round(stats["queue_depth_frac"], 4),
        "param_staleness_max": stats["param_staleness_max"],
        "traj_staleness_max": stats["traj_staleness_max"],
        "traj_staleness_avg": round(stats["traj_staleness_avg"], 3),
        "actor_cache_sizes": stats["actor_cache_sizes"],
        "steady_windows": updates,
        "actor_devices": n_actors,
        "learner_devices": n_devices - n_actors if n_devices > 1 else 1,
        "worker_restarts": stats["worker_restarts"],
        "torn_rejected": stats["torn_rejected"],
        # step-phase breakdown of the learner window (telemetry/spans.py):
        # queue.wait vs rollout vs update.dispatch vs param.broadcast
        # fractions — the tuning signal for traj_queue_slots/max_staleness
        "phase_breakdown": stats["phase_breakdown"],
        "phase_frac_sum": _phase_frac_sum(stats["phase_breakdown"]),
        # ISSUE 12 acceptance gates: compile-once actor inference across the
        # steady windows under the armed guard, and beating the adapter path
        "cache_size_one": cache_ok,
        "beats_adapter_baseline": beats,
        "gate_failed": not (cache_ok and beats),
    }


def bench_dcn() -> dict:
    """Cross-host (fake-DCN) pod transport benchmark (``--mode dcn``,
    ISSUE 19).

    Two measured phases over a REAL 2-process pod (``SHEEPRL_FAKE_DCN``
    learner + actor cells; segments and params cross the process boundary
    over the learner front's HTTP transport):

    * **throughput** — a fresh ppo_decoupled pod run to
      ``BENCH_DCN_STEPS``; rank 0's ``POD_STATS_JSON`` line yields the
      DCN counters: param-broadcast publishes/bytes, segment intake
      rate/bytes, push retries/waits, staleness ledgers;
    * **restart** — the same pod relaunched with a raised step budget and
      ``checkpoint.resume_from=auto`` (exactly what the pod supervisor
      appends after a preemption); the bench times spawn → first NEW
      committed snapshot: the end-to-end pod recovery latency (init +
      coordinated resume + warmup + first window + all-rank commit).

    GATES the never-drop contract across the DCN: every segment the actor
    cell ever enqueued was accepted by the learner front
    (``queue_total_put == segments_accepted``) with zero rejects in a
    clean run.
    """
    import glob as _glob
    import shutil
    import subprocess
    import sys as _sys

    steps = int(os.environ.get("BENCH_DCN_STEPS", 64))
    hosts = max(2, int(os.environ.get("BENCH_DCN_HOSTS", 2)))
    log_dir = "/tmp/bench_dcn"
    shutil.rmtree(log_dir, ignore_errors=True)

    common = [
        "exp=ppo_decoupled",
        "env=dummy",
        "env.id=discrete_dummy",
        "env.max_episode_steps=16",
        "env.num_envs=2",
        "env.sync_env=True",
        "env.capture_video=False",
        "topology=pod",
        "topology.env_workers=2",
        "fabric.devices=auto",
        "fabric.accelerator=cpu",
        "algo.rollout_steps=4",
        "algo.per_rank_batch_size=8",
        "algo.update_epochs=1",
        "algo.mlp_keys.encoder=[state]",
        "algo.run_test=False",
        "checkpoint.every=16",
        "checkpoint.save_last=False",
        "checkpoint.commit_timeout_s=30",
        "buffer.memmap=False",
        "metric.log_level=1",
        "metric.log_every=1",
        f"log_dir={log_dir}",
        "print_config=False",
    ]

    def run_pod(extra: list, timeout_s: float = 420.0) -> tuple:
        env = dict(os.environ)
        env.update({"SHEEPRL_FAKE_DCN": str(hosts), "JAX_PLATFORMS": "cpu"})
        proc = subprocess.Popen(
            [_sys.executable, "-m", "sheeprl_tpu", *common, *extra],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        t0 = time.perf_counter()
        first_commit_s = None
        existing = set(_glob.glob(os.path.join(log_dir, "**", "COMMIT"), recursive=True))
        stats = None
        # line-by-line so the commit watch has real-time resolution
        deadline = time.monotonic() + timeout_s
        for line in proc.stdout:  # type: ignore[union-attr]
            if "POD_STATS_JSON=" in line:
                stats = json.loads(line.split("POD_STATS_JSON=", 1)[1])
            if first_commit_s is None:
                fresh = (
                    set(_glob.glob(os.path.join(log_dir, "**", "COMMIT"), recursive=True))
                    - existing
                )
                if fresh:
                    first_commit_s = time.perf_counter() - t0
            if time.monotonic() > deadline:
                proc.kill()
                break
        rc = proc.wait(timeout=60)
        if rc != 0 or stats is None:
            raise RuntimeError(f"bench_dcn pod run failed (rc={rc}, stats={stats is not None})")
        return stats, first_commit_s, time.perf_counter() - t0

    # ---- phase 1: clean-run DCN throughput --------------------------------
    stats, _, wall = run_pod([f"algo.total_steps={steps}"])
    dcn = stats.get("dcn", {})
    drop = stats.get("zero_drop", {})
    accepted = int(drop.get("segments_accepted", 0))
    rejected = int(drop.get("segments_rejected", 0))
    total_put = int(drop.get("queue_total_put", -1))
    zero_drop_ok = accepted == total_put and rejected == 0 and accepted > 0
    seg_bytes = float(dcn.get("Dcn/segment_bytes", 0.0))
    bc_bytes = float(dcn.get("Dcn/broadcast_bytes", 0.0))
    bc_pubs = max(int(dcn.get("Dcn/broadcast_publishes", 0)), 1)

    # ---- phase 2: restart-to-first-update (the preemption recovery path) --
    _, first_commit_s, _ = run_pod(
        [f"algo.total_steps={steps + 32}", "checkpoint.resume_from=auto"]
    )

    return {
        "metric": (
            f"dcn_segments_per_s (ppo_decoupled pod, {hosts} fake hosts, "
            f"{steps} steps, cpu)"
        ),
        "value": round(accepted / wall, 2),
        "unit": "segments/s",
        "env_steps_per_s": round(stats.get("env_steps_per_s", 0.0), 2),
        "updates_per_s": round(stats.get("updates_per_s", 0.0), 3),
        "traj_mib_per_s": round(seg_bytes / wall / 2**20, 4),
        "broadcast_publishes": int(dcn.get("Dcn/broadcast_publishes", 0)),
        "broadcast_kib_per_publish": round(bc_bytes / bc_pubs / 1024, 1),
        "push_retries": int(dcn.get("rank1/Dcn/push_retries", 0)),
        "backpressured": int(dcn.get("Dcn/backpressured", 0)),
        "param_staleness_max": stats.get("param_staleness_max", 0),
        "traj_staleness_max": stats.get("traj_staleness_max", 0),
        "torn_rejected": stats.get("torn_rejected", 0),
        # pod recovery latency: relaunch with resume_from=auto (what the
        # pod supervisor does after a preemption) -> first NEW all-rank
        # commit.  None means the resumed run never committed in time.
        "restart_to_first_commit_s": (
            round(first_commit_s, 2) if first_commit_s is not None else None
        ),
        # the never-drop contract, measured across a real process boundary
        "zero_drop": {
            "queue_total_put": total_put,
            "segments_accepted": accepted,
            "segments_rejected": rejected,
        },
        "zero_drop_ok": zero_drop_ok,
        "gate_failed": not zero_drop_ok or first_commit_s is None,
    }


def bench_pipeline() -> dict:
    """MPMD pipeline-parallel world-model update bench (``--mode pipeline``,
    ISSUE 16).

    Two measured arms of the SAME DreamerV3 train phase
    (``_build_dv3_train_phase`` — the benchmarked program IS the training
    program):

    * **GSPMD baseline** — data-parallel mesh over every device, the
      ``pipeline`` group off (the monolithic pre-pipeline program);
    * **pipelined** — a ``pipeline`` mesh axis + ``pipeline=2stage``: the
      world-model update runs as the in-trace 1F1B microbatch schedule
      (parallel/pipeline.py, docs/pipeline.md) inside the same ONE jitted
      dispatch.

    Reports updates/s for both arms, the schedule's bubble fraction, and a
    per-stage phase breakdown — ``pipeline.stage.<name>.fwd/.bwd`` spans
    timed over standalone ``compile_stage_pair`` programs built from the
    same stage functions the fused phase pipelines (``make_wm_stages``).
    GATES the ISSUE 16 acceptance: ``steady_compiles == 0`` across both
    armed steady windows, ``cache_size() == 1`` for both phase
    executables, and the span fractions summing to ~1.0.  The speedup
    ratio is reported but NOT gated: fake CPU devices share host cores, so
    the A/B only orders truthfully on real chips.
    """
    # CPU hosts need fake devices for a real pipeline axis — must land in
    # XLA_FLAGS before the backend initializes (no-op if already forced)
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()

    import numpy as np

    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.config.compose import compose
    from sheeprl_tpu.parallel.fabric import build_fabric
    from sheeprl_tpu.utils.profiler import COMPILE_MONITOR
    from sheeprl_tpu.utils.utils import device_sync

    n_devices = len(jax.devices())
    size = os.environ.get("BENCH_PIPE_SIZE", "XS")
    L = int(os.environ.get("BENCH_PIPE_L", 8))
    B = int(os.environ.get("BENCH_PIPE_B", 8))
    U = int(os.environ.get("BENCH_PIPE_U", 1))
    iters = int(os.environ.get("BENCH_PIPE_ITERS", 6))
    stage_iters = int(os.environ.get("BENCH_PIPE_STAGE_ITERS", 5))
    # pipelined-arm mesh: 4-deep pipeline axis when the device count allows,
    # 2-deep otherwise (B must stay divisible by BOTH data axes below)
    if os.environ.get("BENCH_PIPE_MESH"):
        pipe_mesh = os.environ["BENCH_PIPE_MESH"]
    elif n_devices % 4 == 0 and n_devices >= 8:
        pipe_mesh = f"{{data: {n_devices // 4}, pipeline: 4}}"
    else:
        pipe_mesh = f"{{data: {max(1, n_devices // 2)}, pipeline: 2}}"

    common = [
        "exp=dreamer_v3",
        "env=dummy",
        "env.id=discrete_dummy",
        f"algo=dreamer_v3_{size}",
        "algo.cnn_keys.encoder=[rgb]",
        "algo.mlp_keys.encoder=[]",
        f"algo.per_rank_batch_size={B}",
        f"algo.per_rank_sequence_length={L}",
        "algo.max_recompiles=1",
        "fabric.accelerator=auto",
        f"fabric.devices={n_devices}",
        "print_config=False",
    ]

    rng = np.random.default_rng(0)
    block_np = {
        "rgb": rng.integers(0, 255, (U, L, B, 64, 64, 3)).astype(np.uint8),
        "actions": rng.integers(0, 2, (U, L, B, 4)).astype(np.float32),
        "rewards": rng.normal(size=(U, L, B)).astype(np.float32),
        "terminated": np.zeros((U, L, B), np.float32),
        "is_first": np.zeros((U, L, B), np.float32),
    }

    def _arm(extra):
        """One measured arm: build the phase, warm it, then time `iters`
        steady windows under the armed H2D transfer guard."""
        cfg = compose(common + extra)
        fabric = build_fabric(cfg)
        train_phase, params, opt_state = _build_dv3_train_phase(fabric, cfg)
        block = fabric.shard_batch(
            {k: jnp.asarray(v) for k, v in block_np.items()}, axis=2
        )
        key = jax.random.PRNGKey(0)
        t0 = time.perf_counter()
        params, opt_state, metrics = train_phase(params, opt_state, block, key, jnp.int32(0))
        device_sync((params, metrics))
        first_call_s = time.perf_counter() - t0
        # counters pre-staged OUTSIDE the guard (eager host ints are H2D)
        steps_dev = [jax.device_put(np.int32(i + 1)) for i in range(iters)]
        n0, _ = COMPILE_MONITOR.totals()
        t0 = time.perf_counter()
        with jax.transfer_guard_host_to_device("disallow"):
            for i in range(iters):
                params, opt_state, metrics = train_phase(
                    params, opt_state, block, key, steps_dev[i]
                )
        device_sync((params, metrics))
        wall = time.perf_counter() - t0
        n1, _ = COMPILE_MONITOR.totals()
        return {
            "updates_per_s": U * iters / wall,
            "first_call_s": first_call_s,
            "steady_compiles": n1 - n0,
            "cache_size": train_phase.cache_size(),
            "mesh_shape": {k: int(v) for k, v in fabric.mesh.shape.items()},
        }, cfg, fabric

    base, _, _ = _arm([f"fabric.mesh_shape={{data: {n_devices}}}"])
    pipe_arm, pipe_cfg, pipe_fabric = _arm(
        [f"fabric.mesh_shape={pipe_mesh}", "pipeline=2stage"]
    )

    # ---- per-stage phase breakdown (standalone stage programs) ------------
    from gymnasium import spaces

    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import make_wm_stages
    from sheeprl_tpu.parallel.pipeline import (
        compile_stage_pair, resolve_pipeline, split_microbatches,
    )
    from sheeprl_tpu.telemetry.spans import SPANS
    from sheeprl_tpu.utils.distribution import OneHotCategorical

    spec = resolve_pipeline(pipe_cfg)
    obs_space = spaces.Dict({"rgb": spaces.Box(0, 255, (64, 64, 3), np.uint8)})
    world_model, _, _, agent_params = build_agent(
        pipe_fabric, (4,), False, pipe_cfg, obs_space
    )
    wm_params = agent_params["world_model"]
    _, stage_fns, stage_names = make_wm_stages(pipe_cfg, world_model, ("rgb",), ())

    data = {k: jnp.asarray(v[0]) for k, v in block_np.items()}  # one (L, B, *) update
    noise = jax.vmap(
        lambda kk: OneHotCategorical.sample_noise(
            kk, (B, world_model.stochastic_size, world_model.discrete_size)
        )
    )(jax.random.split(jax.random.PRNGKey(1), L))
    consts = split_microbatches({"data": data, "noise": noise}, spec.microbatches, axis=1)
    const_mb = jax.tree.map(lambda a: a[0], consts)  # one microbatch slice

    programs = []
    carry = None
    for raw, nm in zip(stage_fns, stage_names):
        # the params ride as the differentiable operand so the stage
        # backward measures the REAL 1F1B cost (param grads); the carry and
        # microbatch const are baked in as program constants
        def _stage(p, x, _raw=raw, _carry=carry):
            return _raw(x, _carry, const_mb)

        fwd_c, bwd_c = compile_stage_pair(pipe_fabric, _stage, name=f"pipeline.stage.{nm}")
        out = fwd_c(wm_params, wm_params)  # warm fwd; also the next stage's carry
        px = jax.tree.map(lambda a: a.copy(), wm_params)
        dy = jax.tree.map(jnp.ones_like, out)
        bwd_c(wm_params, px, dy)  # warm bwd (compiles land outside the spans)
        programs.append((nm, fwd_c, bwd_c))
        carry = out

    SPANS.roll_window()
    for _ in range(stage_iters):
        for nm, fwd_c, bwd_c in programs:
            with SPANS.span(f"pipeline.stage.{nm}.fwd"):
                out = fwd_c(wm_params, wm_params)
                device_sync(out)
            # canonical rebinding: bwd DONATES the activation copy and the
            # cotangent — both are freshly created every iteration
            px = jax.tree.map(lambda a: a.copy(), wm_params)
            dy = jax.tree.map(jnp.ones_like, out)
            with SPANS.span(f"pipeline.stage.{nm}.bwd"):
                grads = bwd_c(wm_params, px, dy)
                device_sync(grads)
    breakdown = SPANS.breakdown()

    steady_compiles = base["steady_compiles"] + pipe_arm["steady_compiles"]
    cache_ok = base["cache_size"] == 1 and pipe_arm["cache_size"] == 1
    frac_sum = _phase_frac_sum(breakdown)
    frac_ok = abs(frac_sum - 1.0) < 0.02
    dev = jax.devices()[0]
    return {
        "metric": (
            f"dreamer_v3_{size}_pipelined_updates_per_s "
            f"(S={spec.stages} M={spec.microbatches} 1f1b, mesh {pipe_mesh}, "
            f"B={B} L={L} U={U}, {dev.platform})"
        ),
        "value": round(pipe_arm["updates_per_s"], 3),
        "unit": "updates/s",
        # reported, not gated: fake CPU devices share host cores
        "vs_baseline": round(pipe_arm["updates_per_s"] / base["updates_per_s"], 3),
        "updates_per_s_pipelined": round(pipe_arm["updates_per_s"], 3),
        "updates_per_s_gspmd_baseline": round(base["updates_per_s"], 3),
        "first_call_s_pipelined": round(pipe_arm["first_call_s"], 3),
        "first_call_s_gspmd_baseline": round(base["first_call_s"], 3),
        "pipeline": {
            "stages": spec.stages,
            "microbatches": spec.microbatches,
            "schedule": spec.schedule,
            "stage_names": list(stage_names),
        },
        # the schedule's idle fraction (S-1)/(M+S-1) — docs/pipeline.md
        "bubble_frac": round(spec.bubble_frac, 6),
        "mesh_shape_pipelined": pipe_arm["mesh_shape"],
        "mesh_shape_baseline": base["mesh_shape"],
        "steady_windows": iters,
        # per-stage fwd/bwd wall fractions (pipeline.stage.* spans): the
        # stage-balance tuning signal behind pipeline.stages grouping
        "phase_breakdown": breakdown,
        "phase_frac_sum": frac_sum,
        # ISSUE 16 acceptance gates: compile-once across both armed steady
        # windows + the span fractions accounting for the whole window
        "steady_compiles": steady_compiles,
        "cache_size_one": cache_ok,
        "gate_failed": not (steady_compiles == 0 and cache_ok and frac_ok),
    }


def bench_fault_overhead() -> dict:
    """Zero-overhead gate for the fault-injection layer (docs/resilience.md).

    The engine's contract is that an EMPTY fault plan compiles to a no-op
    (the process-global plan is ``None`` and every instrumented site is a
    single pointer test).  This bench holds it to the number the ISSUE
    names: steady-state DreamerV3 updates/s with fault injection installed-
    but-empty must be within ``BENCH_FAULT_TOL`` (default 2%) of the
    uninstrumented baseline — measured as INTERLEAVED A/B windows over the
    same compiled executable so host noise hits both arms alike — and the
    empty-plan run must emit zero ``Resilience/*`` metrics.

    ``gate_failed: true`` in the payload (and a nonzero exit) on violation.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.config.compose import compose
    from sheeprl_tpu.parallel.fabric import build_fabric
    from sheeprl_tpu.resilience.faults import FaultPlan, clear_plan, fault_point, install_plan
    from sheeprl_tpu.utils.profiler import RESILIENCE_MONITOR
    from sheeprl_tpu.utils.utils import device_sync

    size = os.environ.get("BENCH_SIZE", "XS")
    L = int(os.environ.get("BENCH_L", 8))
    B = int(os.environ.get("BENCH_B", 4))
    U = int(os.environ.get("BENCH_U", 2))
    samples = int(os.environ.get("BENCH_FAULT_SAMPLES", 12))
    tol = float(os.environ.get("BENCH_FAULT_TOL", 0.02))

    cfg = compose(
        [
            "exp=dreamer_v3",
            "env=dummy",
            "env.id=discrete_dummy",
            f"algo=dreamer_v3_{size}",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[]",
            f"algo.per_rank_batch_size={B}",
            f"algo.per_rank_sequence_length={L}",
        ]
    )
    fabric = build_fabric(cfg)
    rng = np.random.default_rng(0)
    block = {
        "rgb": jnp.asarray(rng.integers(0, 255, (U, L, B, 64, 64, 3)).astype(np.uint8)),
        "actions": jnp.asarray(rng.integers(0, 2, (U, L, B, 4)).astype(np.float32)),
        "rewards": jnp.asarray(rng.normal(size=(U, L, B)).astype(np.float32)),
        "terminated": jnp.zeros((U, L, B), jnp.float32),
        "is_first": jnp.zeros((U, L, B), jnp.float32),
    }
    train_phase, params, opt_state = _build_dv3_train_phase(fabric, cfg)
    block = fabric.shard_batch(block, axis=2)
    key = jax.random.PRNGKey(0)

    # warm up once; both arms reuse this one executable
    params, opt_state, metrics = train_phase(params, opt_state, block, key, jnp.int32(0))
    device_sync((params, metrics))

    RESILIENCE_MONITOR.reset()

    step = 0

    def one_dispatch(hooked: bool):
        nonlocal params, opt_state, step
        t0 = time.perf_counter()
        if hooked:
            # the instrumented arm must HIT a real site or the gate is
            # vacuous: real train iterations poll fabric.copy_to (player
            # sync) once per iteration, so pay the same hook here.  The
            # baseline arm deliberately does NOT call it — a regression of
            # the disabled fast path must show up as a DIFFERENCE, not
            # cancel out across both arms.
            fault_point("fabric.copy_to")
        params, opt_state, metrics = train_phase(
            params, opt_state, block, key, jnp.int32(step)
        )
        device_sync((params, metrics))
        step += 1
        return time.perf_counter() - t0

    one_dispatch(False)  # discard one warm-in dispatch (caches, allocator)

    # Estimator chosen for a noisy shared host: a dispatch only ever gets
    # SLOWED by contention (noise is strictly one-sided), so each arm's
    # MIN-of-N dispatch time is a tight estimate of its attainable latency;
    # arms alternate per dispatch so drift cannot systematically favor one.
    baseline, empty_plan = [], []
    for s in range(2 * samples):
        if s % 2 == 0:
            clear_plan()  # fault injection entirely absent, no hook called
            baseline.append(one_dispatch(False))
        else:
            # the user-facing "enabled with an empty plan" spelling —
            # install_plan MUST fold it to None (the zero-overhead contract)
            install_plan(FaultPlan.from_specs([]))
            empty_plan.append(one_dispatch(True))
    clear_plan()

    base = U / min(baseline)  # attainable updates/s, no fault layer
    empty = U / min(empty_plan)  # …with an installed-but-empty plan
    # directional: only a SLOWDOWN of the empty-plan arm is a regression —
    # the arms run near-identical code, so "empty came out faster" is noise
    # and must not fail CI
    overhead = max(0.0, (base - empty) / base)
    leaked = RESILIENCE_MONITOR.metrics()  # must be {} — nothing recorded
    gate_failed = overhead >= tol or bool(leaked)
    return {
        "metric": (
            f"fault_injection_empty_plan_overhead "
            f"(dreamer_v3_{size} B={B} L={L} U={U}, {samples}x interleaved A/B, min-estimator)"
        ),
        "value": round(overhead * 100, 3),
        "unit": "%",
        "vs_baseline": None,
        "steady_updates_per_s_no_plan": round(base, 4),
        "steady_updates_per_s_empty_plan": round(empty, 4),
        "tolerance_pct": tol * 100,
        "resilience_metrics_emitted": leaked,
        "gate_failed": gate_failed,
    }


def bench_telemetry_overhead() -> dict:
    """Zero-overhead gate for the telemetry subsystem (docs/telemetry.md).

    Default-on telemetry (span push/pop per phase, the recorder's span-edge
    events, the tracer tick) must cost <``BENCH_TELEMETRY_TOL`` (default
    2%) of steady-state DreamerV3 updates/s — measured exactly like the
    fault-injection gate: INTERLEAVED A/B windows over the same compiled
    executable, min-of-N per arm (host noise is one-sided), directional
    (only a slowdown of the instrumented arm can fail).  The instrumented
    arm pays the real per-update span load: a top-level rollout span, a
    top-level update.dispatch span (which also ticks the trace scheduler)
    and a nested queue-wait span.

    ``gate_failed: true`` in the payload (and a nonzero exit) on violation.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.config.compose import compose
    from sheeprl_tpu.parallel.fabric import build_fabric
    from sheeprl_tpu.telemetry.spans import SPANS, span
    from sheeprl_tpu.utils.utils import device_sync

    size = os.environ.get("BENCH_SIZE", "XS")
    L = int(os.environ.get("BENCH_L", 8))
    B = int(os.environ.get("BENCH_B", 4))
    U = int(os.environ.get("BENCH_U", 2))
    samples = int(os.environ.get("BENCH_TELEMETRY_SAMPLES", 12))
    tol = float(os.environ.get("BENCH_TELEMETRY_TOL", 0.02))

    cfg = compose(
        [
            "exp=dreamer_v3",
            "env=dummy",
            "env.id=discrete_dummy",
            f"algo=dreamer_v3_{size}",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[]",
            f"algo.per_rank_batch_size={B}",
            f"algo.per_rank_sequence_length={L}",
        ]
    )
    fabric = build_fabric(cfg)
    rng = np.random.default_rng(0)
    block = {
        "rgb": jnp.asarray(rng.integers(0, 255, (U, L, B, 64, 64, 3)).astype(np.uint8)),
        "actions": jnp.asarray(rng.integers(0, 2, (U, L, B, 4)).astype(np.float32)),
        "rewards": jnp.asarray(rng.normal(size=(U, L, B)).astype(np.float32)),
        "terminated": jnp.zeros((U, L, B), jnp.float32),
        "is_first": jnp.zeros((U, L, B), jnp.float32),
    }
    train_phase, params, opt_state = _build_dv3_train_phase(fabric, cfg)
    block = fabric.shard_batch(block, axis=2)
    key = jax.random.PRNGKey(0)

    # warm up once; both arms reuse this one executable
    params, opt_state, metrics = train_phase(params, opt_state, block, key, jnp.int32(0))
    device_sync((params, metrics))

    step = 0

    def one_dispatch(instrumented: bool):
        nonlocal params, opt_state, step
        t0 = time.perf_counter()
        if instrumented:
            # the real per-update span load of an instrumented train loop:
            # rollout + nested queue wait, then the train dispatch (whose
            # top-level span also ticks the trace scheduler)
            with span("rollout"):
                with span("queue.wait"):
                    pass
            with span("update.dispatch"):
                params, opt_state, metrics = train_phase(
                    params, opt_state, block, key, jnp.int32(step)
                )
        else:
            params, opt_state, metrics = train_phase(
                params, opt_state, block, key, jnp.int32(step)
            )
        device_sync((params, metrics))
        step += 1
        return time.perf_counter() - t0

    one_dispatch(False)  # discard one warm-in dispatch (caches, allocator)

    # interleaved A/B, min-of-N estimator — the fault_overhead pattern:
    # noise on a shared host only ever SLOWS a dispatch, so each arm's
    # minimum is a tight attainable-latency estimate, and alternating
    # arms keeps drift from systematically favoring one
    baseline, instrumented = [], []
    for s in range(2 * samples):
        if s % 2 == 0:
            SPANS.enabled = False
            baseline.append(one_dispatch(False))
        else:
            SPANS.enabled = True
            instrumented.append(one_dispatch(True))
    SPANS.enabled = True
    phase_breakdown = SPANS.breakdown()

    base = U / min(baseline)
    instr = U / min(instrumented)
    # directional: only a SLOWDOWN of the instrumented arm is a regression
    overhead = max(0.0, (base - instr) / base)
    gate_failed = overhead >= tol
    return {
        "metric": (
            f"telemetry_span_overhead "
            f"(dreamer_v3_{size} B={B} L={L} U={U}, {samples}x interleaved A/B, min-estimator)"
        ),
        "value": round(overhead * 100, 3),
        "unit": "%",
        "vs_baseline": None,
        "steady_updates_per_s_disabled": round(base, 4),
        "steady_updates_per_s_instrumented": round(instr, 4),
        "tolerance_pct": tol * 100,
        "phase_breakdown": phase_breakdown,
        "phase_frac_sum": _phase_frac_sum(phase_breakdown),
        "gate_failed": gate_failed,
    }


def bench_health_overhead() -> dict:
    """Cost gate for the default-on training-health sentinels
    (resilience/health.py, docs/supervisor.md).

    The non-finite guard compiles INTO the update dispatch: after the
    train phase's own math it reduces ``isfinite`` over the loss and the
    fresh params and selects old-vs-new — extra device work every window,
    so unlike the fault/telemetry gates the two arms here are genuinely
    DIFFERENT executables: A is the health-guarded DreamerV3 train phase
    (``health.enabled=true``, the default), B is the same phase with the
    sentinel compiled out.  Both are AOT-warmed, then timed as interleaved
    A/B windows with the min-of-N estimator (host noise is one-sided);
    the guarded arm must stay within ``BENCH_HEALTH_TOL`` (default 2%).

    ``gate_failed: true`` in the payload (and a nonzero exit) on violation.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.config.compose import compose
    from sheeprl_tpu.parallel.fabric import build_fabric
    from sheeprl_tpu.resilience.health import HealthSentinel
    from sheeprl_tpu.utils.utils import device_sync

    size = os.environ.get("BENCH_SIZE", "XS")
    L = int(os.environ.get("BENCH_L", 8))
    B = int(os.environ.get("BENCH_B", 4))
    U = int(os.environ.get("BENCH_U", 2))
    samples = int(os.environ.get("BENCH_HEALTH_SAMPLES", 12))
    tol = float(os.environ.get("BENCH_HEALTH_TOL", 0.02))

    cfg = compose(
        [
            "exp=dreamer_v3",
            "env=dummy",
            "env.id=discrete_dummy",
            f"algo=dreamer_v3_{size}",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[]",
            f"algo.per_rank_batch_size={B}",
            f"algo.per_rank_sequence_length={L}",
        ]
    )
    fabric = build_fabric(cfg)
    rng = np.random.default_rng(0)
    block = {
        "rgb": jnp.asarray(rng.integers(0, 255, (U, L, B, 64, 64, 3)).astype(np.uint8)),
        "actions": jnp.asarray(rng.integers(0, 2, (U, L, B, 4)).astype(np.float32)),
        "rewards": jnp.asarray(rng.normal(size=(U, L, B)).astype(np.float32)),
        "terminated": jnp.zeros((U, L, B), jnp.float32),
        "is_first": jnp.zeros((U, L, B), jnp.float32),
    }
    train_phase, params, opt_state = _build_dv3_train_phase(fabric, cfg)
    block = fabric.shard_batch(block, axis=2)
    key = jax.random.PRNGKey(0)

    sentinel = HealthSentinel(cfg.get("health") or {}, fabric)
    guarded = fabric.compile(
        sentinel.wrap(train_phase),
        name="bench.health_guarded",
        donate_argnums=(0, 1, 2),
    )

    # per-arm state chains (the arms are different executables and both
    # donate their params/opt-state — each must consume only its own)
    p_a = jax.tree.map(jnp.copy, params)
    o_a = jax.tree.map(jnp.copy, opt_state)
    p_b, o_b = params, opt_state
    h = sentinel.init_state()

    # warm both executables before timing anything
    h, p_a, o_a, m = guarded(h, p_a, o_a, block, key, jnp.int32(0))
    device_sync((p_a, m))
    p_b, o_b, m = train_phase(p_b, o_b, block, key, jnp.int32(0))
    device_sync((p_b, m))

    step = 0

    def one_dispatch(guarded_arm: bool):
        nonlocal p_a, o_a, p_b, o_b, h, step
        t0 = time.perf_counter()
        if guarded_arm:
            h, p_a, o_a, m = guarded(h, p_a, o_a, block, key, jnp.int32(step))
            device_sync((p_a, m))
        else:
            p_b, o_b, m = train_phase(p_b, o_b, block, key, jnp.int32(step))
            device_sync((p_b, m))
        step += 1
        return time.perf_counter() - t0

    one_dispatch(False)  # discard one warm-in dispatch (caches, allocator)
    one_dispatch(True)

    # interleaved A/B, min-of-N estimator (the fault_overhead pattern)
    baseline, instrumented = [], []
    for s in range(2 * samples):
        if s % 2 == 0:
            baseline.append(one_dispatch(False))
        else:
            instrumented.append(one_dispatch(True))

    base = U / min(baseline)
    instr = U / min(instrumented)
    # directional: only a SLOWDOWN of the guarded arm is a regression
    overhead = max(0.0, (base - instr) / base)
    gate_failed = overhead >= tol or guarded.cache_size() != 1
    return {
        "metric": (
            f"health_sentinel_overhead "
            f"(dreamer_v3_{size} B={B} L={L} U={U}, {samples}x interleaved A/B, min-estimator)"
        ),
        "value": round(overhead * 100, 3),
        "unit": "%",
        "vs_baseline": None,
        "steady_updates_per_s_unguarded": round(base, 4),
        "steady_updates_per_s_guarded": round(instr, 4),
        "tolerance_pct": tol * 100,
        "guarded_cache_size": guarded.cache_size(),
        "gate_failed": gate_failed,
    }


def bench_lint() -> dict:
    """graftlint wall-time gate (``--mode lint``, ISSUE 15).

    Times the whole-package static-analysis run (the run_ci stage 14 /
    tier-1 workload) and gates it like any other perf surface: findings
    mean the repo broke the zero-unsuppressed invariant, stale baseline
    entries mean a fixed finding kept its ledger entry, and a >60 s wall
    means the analyzer outgrew its CI budget.  Pure host work — no jax
    dispatch, no accelerator involvement."""
    from sheeprl_tpu.analysis import Baseline, DEFAULT_BASELINE, run_analysis

    t0 = time.perf_counter()
    report = run_analysis(baseline=Baseline.load(DEFAULT_BASELINE))
    wall = time.perf_counter() - t0

    budget_s = float(os.environ.get("BENCH_LINT_BUDGET_S", 60.0))
    gate_failed = bool(
        report.findings or report.stale_baseline or wall > budget_s
    )
    return {
        "metric": f"graftlint_wall (whole sheeprl_tpu/, {report.files_analyzed} files)",
        "value": round(wall, 3),
        "unit": "s",
        "vs_baseline": None,
        "files_analyzed": report.files_analyzed,
        "unsuppressed_findings": len(report.findings),
        "findings_by_rule": report.counts(),
        "baselined": len(report.baselined),
        "comment_suppressed": len(report.suppressed),
        "stale_baseline_entries": len(report.stale_baseline),
        "budget_s": budget_s,
        "gate_failed": gate_failed,
    }


def _run_bench() -> dict:
    target = os.environ.get("BENCH_TARGET", "dreamer_v3")
    if target == "lint":
        return bench_lint()
    if target == "serve":
        return bench_serve()
    if target == "serve_fleet":
        return bench_serve_fleet()
    if target == "replay":
        return bench_device_replay()
    if target == "fault_overhead":
        return bench_fault_overhead()
    if target == "telemetry_overhead":
        return bench_telemetry_overhead()
    if target == "health_overhead":
        return bench_health_overhead()
    if target == "env":
        return bench_env()
    if target == "population":
        return bench_population()
    if target == "sebulba":
        return bench_sebulba()
    if target == "dcn":
        return bench_dcn()
    if target == "pipeline":
        return bench_pipeline()
    if target in BASELINE_CPU_WALL_CLOCK_S:
        return bench_cpu_wall_clock(target)
    return bench_dreamer_v3()


if __name__ == "__main__":
    import sys

    # `--mode <target>` CLI alias for BENCH_TARGET (e.g. `bench.py --mode
    # serve`); the env var form keeps working
    if "--mode" in sys.argv:
        idx = sys.argv.index("--mode")
        if idx + 1 >= len(sys.argv):
            raise SystemExit("--mode requires a target (serve, dreamer_v3, ppo, ...)")
        os.environ["BENCH_TARGET"] = sys.argv[idx + 1]
    target = os.environ.get("BENCH_TARGET", "dreamer_v3")

    # The body runs in THIS process, on the chip.  There is no CPU fallback:
    # without a tpu platform the bench exits non-zero and prints no metric,
    # unless the caller asked for the CPU itself with JAX_PLATFORMS=cpu (a
    # functional run whose metric names say "cpu").  graftlint is pure host
    # AST work and touches no backend.
    if target != "lint" and os.environ.get("JAX_PLATFORMS") != "cpu":
        import jax

        platform = jax.devices()[0].platform
        if platform != "tpu":
            print(
                f"[bench] no chip: JAX's default platform is '{platform}', not 'tpu' "
                "(set JAX_PLATFORMS=cpu yourself for a functional CPU run)",
                file=sys.stderr,
            )
            sys.exit(2)
    result = _run_bench()
    # every mode's payload is self-describing: mode, git SHA and
    # host/device inventory ride along (BENCH_*.json archaeology must
    # not need the shell history that produced the file)
    result.update(_bench_stamp(target))
    print(json.dumps(result))
    if result.get("gate_failed"):
        # the fault-overhead gate is an ASSERTION: empty-plan steady
        # state drifted beyond tolerance (or Resilience/* leaked)
        sys.exit(1)
