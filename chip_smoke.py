#!/usr/bin/env python3
"""Prove that the main path starts, compiles and finishes on the attached TPU.

    python chip_smoke.py              # one chip: the `dv3` and `anakin` phases
    python chip_smoke.py --chips 4    # only the 1-chip vs 4-chip DV3-S comparison
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # tiny widths, no chip

The parent process never touches JAX.  Each phase is ONE child process that
calls the normal entry point, ``sheeprl_tpu.cli.run`` (what ``python -m
sheeprl_tpu <overrides>`` runs), with probes of this script's own around it;
the phases run one after another, so exactly one process holds the chip at a
time.  Every assertion is made in the child from what the run left behind
(metric log, checkpoint directory, compile monitor, device memory stats, the
arrays its programs were fed).  Any failed assertion, non-zero child or missing
accelerator makes the last line ``{"ok": false, ...}`` and the exit code
non-zero; nothing is caught and passed over.

Last line of stdout on success, exactly:
    {"ok": true, "device": {"platform": "tpu", "kind": "<device_kind>", "count": <n>}}
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent

#: relative tolerance tests/test_regression/test_golden.py already allows a
#: foreign platform (RTOL_FOREIGN) — what two layouts of one bf16 update may differ by
RTOL_FOREIGN = 5e-2

# --------------------------------------------------------------------------
# the commands (every entry differs from the exp's defaults and is printed)
# --------------------------------------------------------------------------

_RUN = [
    "metric/logger=csv",  # a metric log the phase can read back without TensorBoard
    "print_config=False",
    "run_name=smoke",
    "fabric.accelerator=tpu",
]

#: DV3-S at the published widths (dense 512 x 2 MLP layers, CNN multiplier 32,
#: recurrent 512, 32x32 discrete latent, horizon 15), B=16 L=64 on 64x64x3
#: pixels.  buffer.size (1e6) and algo.learning_starts (1024) stay at the exp
#: defaults; total_steps is cut from 5e6 to the post-learning_starts burst
#: (1024 updates) plus 256 further updates.
DV3 = [
    "exp=dreamer_v3",
    "algo=dreamer_v3_S",
    "env=jax_forage",
    "fabric.devices=1",
    "fabric.precision=bf16-mixed",
    "algo.total_steps=1280",
    "algo.max_recompiles=1",  # train window U=128 (burst) and U=4 (steady): one recompile
    "metric.log_every=64",
    *_RUN,
]

#: PPO at the exp's default widths on the pixel jax env: the fused Anakin
#: rollout+update executable, 8 iterations of 4 envs x 128 steps.
ANAKIN = [
    "exp=ppo",
    "env=jax_multiroom",
    "algo.cnn_keys.encoder=[rgb]",
    "algo.mlp_keys.encoder=[]",
    "fabric.devices=1",
    "algo.max_recompiles=1",
    "algo.total_steps=4096",
    "metric.log_every=512",
    *_RUN,
]

#: The four-chip comparison: the same DV3-S command on one chip and on four
#: ({data: 4}, 4 sequences per chip, same global batch, same seed).  The first
#: window is cut to ONE update (per_rank_pretrain_steps=1 makes Ratio owe one
#: step at learning_starts instead of the 1024-update burst) so the two
#: children's FIRST-update losses can be compared.  Gradient steps are owed
#: per rank, so the 256 env steps that follow are 256 updates on one chip
#: and 64 on four.
DV3_COMPARE = [a for a in DV3 if not a.startswith("fabric.devices=")] + [
    "algo.per_rank_pretrain_steps=1",
]

#: --rehearse: the same phases at tiny widths on whatever JAX finds (the
#: sandbox CPU).  The device ring is forced on because `auto` turns it off
#: without an accelerator.
_REHEARSE_DV3 = [
    "algo=dreamer_v3_XS",
    "algo.dense_units=16",
    "algo.mlp_layers=1",
    "algo.horizon=4",
    "algo.world_model.encoder.cnn_channels_multiplier=4",
    "algo.world_model.recurrent_model.recurrent_state_size=16",
    "algo.world_model.transition_model.hidden_size=16",
    "algo.world_model.representation_model.hidden_size=16",
    "algo.world_model.discrete_size=4",
    "algo.world_model.stochastic_size=4",
    "algo.per_rank_sequence_length=8",
    "algo.learning_starts=64",
    "buffer.size=4096",
    "buffer.device=True",
    "fabric.precision=32-true",
    "algo.total_steps=160",
]
_REHEARSE_PPO = [
    "algo.rollout_steps=16", "algo.per_rank_batch_size=16", "algo.update_epochs=2",
    "algo.total_steps=512",
]


def phase_overrides(phase: str, rehearse: bool) -> List[str]:
    if phase == "dv3":
        args = list(DV3)
    elif phase == "anakin":
        args = list(ANAKIN)
    elif phase == "dv3_1chip":
        args = DV3_COMPARE + ["fabric.devices=1", "algo.per_rank_batch_size=16"]
    elif phase == "dv3_4chip":
        args = DV3_COMPARE + ["fabric.devices=4", "algo.per_rank_batch_size=4"]
    else:
        raise SystemExit(f"unknown phase {phase!r}")
    if rehearse:  # later overrides win: append the tiny widths, swap the preset
        args = [a for a in args if a != "algo=dreamer_v3_S"] + ["fabric.accelerator=auto"]
        if phase == "anakin":
            args += _REHEARSE_PPO
        else:
            # 1-chip child B=8, 4-chip child 4 x B=2: the same global batch
            batch = "2" if phase == "dv3_4chip" else "8"
            args += _REHEARSE_DV3 + [f"algo.per_rank_batch_size={batch}"]
    return args


# --------------------------------------------------------------------------
# child: one phase in its own process
# --------------------------------------------------------------------------

class Checks:
    """Named assertions: all are evaluated and printed, any failure fails the phase."""

    def __init__(self) -> None:
        self.failed: List[str] = []

    def __call__(self, name: str, ok: bool, detail: Any = "") -> None:
        print(f"  [{'ok' if ok else 'FAIL'}] {name}{': ' + str(detail) if detail != '' else ''}", flush=True)
        if not ok:
            self.failed.append(name)


def _platforms(tree: Any) -> List[str]:
    import jax

    found = set()
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            found |= {d.platform for d in leaf.devices()}
    return sorted(found)


class ProgramProbe:
    """Stands in for one ``fabric.compile`` program: times its first dispatch
    (fenced), notes where its inputs and outputs live, and keeps every
    dispatch's (tiny) auxiliary outputs.  Everything else is the real
    ``AOTFunction``."""

    def __init__(self, aot: Any) -> None:
        self.aot = aot
        self.calls = 0
        self.updates = 0
        self.first_call_s: Optional[float] = None
        self.in_platforms: List[str] = []
        self.out_platforms: List[str] = []
        self.aux: List[Any] = []

    def __getattr__(self, name: str) -> Any:
        return getattr(self.aot, name)

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        import jax

        if any(isinstance(x, jax.core.Tracer) for x in jax.tree.leaves((args, kwargs))):
            return self.aot(*args, **kwargs)  # inlined into another program
        first = self.calls == 0
        if first:
            self.in_platforms = _platforms((args, kwargs))
            t0 = time.perf_counter()
        out = self.aot(*args, **kwargs)
        if first:
            jax.block_until_ready(out)
            self.first_call_s = time.perf_counter() - t0
            self.out_platforms = _platforms(out)
        self.calls += 1
        self.updates += int(kwargs.get("n_samples", 1))
        if isinstance(out, tuple):
            self.aux.append(out[-1])  # the program's metrics/stats: a few scalars
        return out


def _honest_fence(checks: Checks, rehearse: bool) -> None:
    """``block_until_ready`` (what ``utils.device_sync`` is) must wait for the
    device: the fenced wall time of a chain of dependent matmuls scales with
    the chain's length and agrees with a fence that materialises a value."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.utils.utils import device_sync

    n = 512 if rehearse else 4096
    w = jnp.full((n, n), 0.01, jnp.bfloat16)

    @partial(jax.jit, static_argnums=1)
    def chain(x, length):
        return jax.lax.fori_loop(0, length, lambda i, x: (x @ w).astype(jnp.bfloat16) * 0.01, x)

    x = jnp.ones((n, n), jnp.bfloat16)
    short, long = 16, 128

    def fenced(length: int, fence) -> float:
        t0 = time.perf_counter()
        fence(chain(x, length))
        return time.perf_counter() - t0

    def materialise(y) -> None:
        float(np.asarray(y[0, 0]))

    for length in (short, long):  # compile + warm both programs and both fences
        fenced(length, device_sync), fenced(length, materialise)
    median = lambda reads: sorted(reads)[len(reads) // 2]  # noqa: E731
    times = {length: median([fenced(length, device_sync) for _ in range(5)]) for length in (short, long)}
    materialised = median([fenced(long, materialise) for _ in range(5)])
    ratio = times[long] / times[short]
    print(
        f"  fence: {short} matmuls {times[short] * 1e3:.2f} ms, {long} matmuls "
        f"{times[long] * 1e3:.2f} ms (x{ratio:.2f} for x{long // short} the work), "
        f"D2H-materialised fence {materialised * 1e3:.2f} ms",
        flush=True,
    )
    if rehearse:  # a property of the chip: host timings here are too noisy to assert on
        return
    checks("block_until_ready is honest (time scales with the chain)", ratio > (long / short) / 2, f"x{ratio:.2f}")
    checks(
        "block_until_ready agrees with a materialising fence",
        0.5 < times[long] / materialised < 2.0,
        f"{times[long] * 1e3:.2f} vs {materialised * 1e3:.2f} ms",
    )


def _read_metric_log(log_root: Path) -> Dict[str, List[float]]:
    import csv

    rows: Dict[str, List[float]] = {}
    for path in sorted(log_root.glob("**/metrics.csv")):
        with open(path) as f:
            for row in csv.DictReader(f):
                rows.setdefault(row["name"], []).append(float(row["value"]))
    return rows


def run_child(phase: str, out_dir: Path, rehearse: bool) -> int:
    """One phase: probes on, ``cli.run``, then the assertions.  Returns the exit code."""
    import sheeprl_tpu  # noqa: F401  (the script alone, without the program, fails here)
    from sheeprl_tpu.parallel import fabric as fabric_mod

    checks = Checks()
    info: Dict[str, Any] = {"phase": phase}
    cache_dir = fabric_mod.ensure_compilation_cache()
    print(
        f"  compile cache: {cache_dir} "
        f"({'JAX_COMPILATION_CACHE_DIR' if 'JAX_COMPILATION_CACHE_DIR' in os.environ else 'in-checkout default'})",
        flush=True,
    )

    import jax
    import jaxlib
    import numpy as np

    devs = jax.devices()
    info["device"] = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__}
    try:
        import libtpu

        versions["libtpu"] = libtpu.__version__
    except ImportError:
        pass
    print(f"  device: {json.dumps(info['device'])}  versions: {json.dumps(versions)}", flush=True)
    if not rehearse:
        checks("JAX's default platform is tpu", devs[0].platform == "tpu", devs[0].platform)
        if checks.failed:
            return _finish(phase, out_dir, info, checks)
    want_platform = devs[0].platform if rehearse else "tpu"
    if phase in ("dv3", "dv3_1chip"):
        _honest_fence(checks, rehearse)

    # ---- probes (this script's own; the package is not told it is watched) ----
    programs: Dict[str, ProgramProbe] = {}
    rings: List[Any] = []
    ring_alloc: Dict[str, int] = {}
    real_compile = fabric_mod.Fabric.compile

    def probed_compile(self, fn, **kwargs):
        probe = ProgramProbe(real_compile(self, fn, **kwargs))
        programs[probe.aot.name] = probe
        return probe

    fabric_mod.Fabric.compile = probed_compile

    from sheeprl_tpu.data import device_replay

    real_init, real_add = device_replay.DeviceReplay.__init__, device_replay.DeviceReplay.add

    def probed_init(self, *args, **kwargs):
        real_init(self, *args, **kwargs)
        rings.append(self)

    def probed_add(self, data, indices=None):
        if not self.empty:
            return real_add(self, data, indices)
        stats = lambda: (devs[0].memory_stats() or {}).get("bytes_in_use", 0)  # noqa: E731
        before = stats()
        real_add(self, data, indices)
        jax.block_until_ready(self.buffers)
        ring_alloc.update(before=before, after=stats())

    device_replay.DeviceReplay.__init__, device_replay.DeviceReplay.add = probed_init, probed_add

    # ---- the run, through the entry point a user calls ----
    overrides = phase_overrides(phase, rehearse) + [f"log_dir={out_dir / phase / 'logs'}"]
    from sheeprl_tpu.cli import run
    from sheeprl_tpu.config.compose import compose

    cfg = compose(overrides)
    t0 = time.perf_counter()
    run(overrides)
    info["wall_s"] = round(time.perf_counter() - t0, 2)

    # ---- what the run left behind ----
    from sheeprl_tpu.checkpoint import latest_checkpoint, load_checkpoint, verify_checkpoint
    from sheeprl_tpu.utils.profiler import COMPILE_MONITOR

    info["compiles"] = {
        name: {"count": st["count"], "seconds": st["seconds"]}
        for name, st in COMPILE_MONITOR.summary().items()
    }
    info["first_dispatch_s"] = {
        name: round(p.first_call_s, 3) for name, p in programs.items() if p.first_call_s is not None
    }
    info["env_steps"] = int(cfg.algo.total_steps)
    mem = [d.memory_stats() or {} for d in devs]
    info["peak_bytes_in_use"] = [m.get("peak_bytes_in_use") for m in mem]
    info["bytes_limit"] = mem[0].get("bytes_limit")
    for name, p in programs.items():
        limit = p.aot.max_recompiles
        checks(
            f"{name}: recompiles inside algo.max_recompiles",
            limit is None or p.aot._compile_count - 1 <= int(limit),
            f"{p.aot._compile_count} executables, limit {limit}",
        )

    log = _read_metric_log(out_dir / phase / "logs")
    losses = {k: v for k, v in log.items() if k.startswith("Loss/")}
    checks("the metric log has losses", bool(losses), sorted(losses))
    checks(
        "every loss in the metric log is finite",
        bool(losses) and all(math.isfinite(x) for v in losses.values() for x in v),
        {k: round(v[-1], 4) for k, v in losses.items()},
    )

    ckpt_roots = sorted((out_dir / phase / "logs").glob("**/checkpoint"))
    ckpt = latest_checkpoint(ckpt_roots[-1]) if ckpt_roots else None
    checks("a checkpoint was committed", ckpt is not None and not verify_checkpoint(ckpt), ckpt)
    if ckpt is not None:
        state = load_checkpoint(ckpt)
        leaves = [np.asarray(x) for x in jax.tree.leaves(state["agent"])]
        n_params = int(sum(x.size for x in leaves))
        info["checkpoint"] = {"path": str(ckpt), "agent_params": n_params}
        checks(
            "the checkpoint loads back (finite agent parameters)",
            n_params > 0 and all(np.isfinite(x).all() for x in leaves),
            f"{n_params} parameters in {len(leaves)} leaves",
        )

    if phase == "anakin":
        fused = programs.get("ppo.anakin_phase")
        host_loop = programs.get("ppo.train_phase")
        iters = int(cfg.algo.total_steps) // (int(cfg.env.num_envs) * int(cfg.algo.rollout_steps))
        checks(
            "the fused Anakin path was taken (not the JaxToGymAdapter host loop)",
            fused is not None and fused.calls == iters and (host_loop is None or host_loop.calls == 0),
            f"ppo.anakin_phase dispatched {fused.calls if fused else 0}x for {iters} iterations",
        )
        if fused is not None:
            checks("anakin_phase cache_size() == 1", fused.aot.cache_size() == 1, fused.aot.cache_size())
            checks(
                f"anakin_phase inputs and outputs live on {want_platform}",
                fused.in_platforms == [want_platform] and fused.out_platforms == [want_platform],
                f"in {fused.in_platforms} out {fused.out_platforms}",
            )
            info["updates"] = fused.calls
    else:
        _check_dv3(phase, cfg, checks, info, programs, rings, ring_alloc, want_platform, devs)
    return _finish(phase, out_dir, info, checks)


def _check_dv3(phase, cfg, checks, info, programs, rings, ring_alloc, want_platform, devs) -> None:
    import jax
    import numpy as np

    from sheeprl_tpu.data.device_replay import ring_device_bytes

    train = programs.get("dreamer_v3.train_phase_device")
    ring = next((r for r in reversed(rings) if not r.empty), None)
    checks("DeviceReplay was the active buffer", train is not None and train.calls > 0 and ring is not None)
    if train is None or ring is None:
        return
    checks(
        f"every train array lives on {want_platform}",
        train.in_platforms == [want_platform] and train.out_platforms == [want_platform],
        f"in {train.in_platforms} out {train.out_platforms}",
    )
    checks(f"every replay array lives on {want_platform}", _platforms((ring.buffers, ring.cursor)) == [want_platform])
    info["updates"] = train.updates
    info["train_dispatches"] = train.calls
    player = programs.get("dreamer_v3.player_step")
    info["player_ran_on"] = player.in_platforms if player else None

    # the ring: what it cost on the device against its raw bytes
    raw = ring.hbm_bytes
    compiled_says = ring_device_bytes(ring.leaf_specs, ring.capacity, ring.n_envs, ring._sharding)
    info["ring"] = {
        "steps_per_env": ring.capacity,
        "n_envs": ring.n_envs,
        "raw_bytes": raw,
        "compiled_allocation_bytes_per_device": compiled_says,
        "bytes_in_use_delta_device0": ring_alloc.get("after", 0) - ring_alloc.get("before", 0),
        "spill_armed": ring.spill is not None,
    }

    # losses: the first dispatch's window mean against the last one logged
    names = (
        "world_model", "observation", "reward", "state", "continue", "kl",
        "policy", "value", "post_entropy", "prior_entropy",
    )
    first = {n: float(np.asarray(v)) for n, v in zip(names, train.aux[0])}
    last = {n: float(np.asarray(v)) for n, v in zip(names, train.aux[-1])}
    info["first_update_losses"], info["last_update_losses"] = first, last
    checks(
        "the world-model loss fell over the run",
        math.isfinite(last["world_model"]) and last["world_model"] < first["world_model"],
        f"{first['world_model']:.2f} -> {last['world_model']:.2f} over {train.updates} updates",
    )

    if phase == "dv3_4chip":
        n = len(ring.buffers["rgb"].sharding.device_set)
        spec = ring.buffers["rgb"].sharding.spec
        checks("the replay ring is sharded over 4 distinct devices on `data`", n == 4 and "data" in spec, f"{n} devices, spec {spec}")
        batch = jax.jit(
            lambda b, c, k: ring.sample_sequences(
                b, c, k, int(cfg.algo.per_rank_batch_size) * 4, int(cfg.algo.per_rank_sequence_length), 1
            )
        )(ring.buffers, ring.cursor, jax.random.PRNGKey(0))["rgb"]
        n = len(batch.sharding.device_set)
        checks("a sampled batch leaf is sharded over 4 distinct devices on `data`", n == 4 and "data" in batch.sharding.spec, f"{batch.shape} on {n} devices, spec {batch.sharding.spec}")
        if devs[0].memory_stats():
            held = [d.memory_stats()["bytes_in_use"] for d in devs[:4]]
        else:  # the rehearsal's CPU devices report no stats: count the ring's shards instead
            held = [sum(s.data.nbytes for s in ring.buffers["rgb"].addressable_shards if s.device == d) for d in devs[:4]]
        info["bytes_held_per_device"] = held
        checks("every one of the four devices holds bytes", len(held) == 4 and all(b > 0 for b in held), held)


def _finish(phase: str, out_dir: Path, info: Dict[str, Any], checks: Checks) -> int:
    info["failed"] = checks.failed
    (out_dir / phase).mkdir(parents=True, exist_ok=True)
    (out_dir / phase / "result.json").write_text(json.dumps(info, indent=1, default=str))
    shown = {k: v for k, v in info.items() if k not in ("phase", "failed", "device")}
    print(f"  info (not a result): {json.dumps(shown, default=str)}", flush=True)
    return 1 if checks.failed else 0


# --------------------------------------------------------------------------
# parent: never imports JAX
# --------------------------------------------------------------------------

def run_phase(phase: str, out_dir: Path, rehearse: bool, timeout_s: int) -> Optional[Dict[str, Any]]:
    """Run one phase as a child; its result.json when it exited 0, else None."""
    print(f"=== phase {phase}: python -m sheeprl_tpu {' '.join(phase_overrides(phase, rehearse))}", flush=True)
    assert "jax" not in sys.modules, "the parent must stay off JAX: a parent that holds the chip starves its children"
    shutil.rmtree(out_dir / phase, ignore_errors=True)  # an earlier run's logs are not this run's
    result = out_dir / phase / "result.json"
    cmd = [sys.executable, str(Path(__file__).resolve()), "--child", phase, "--out", str(out_dir)]
    child = subprocess.Popen(cmd + (["--rehearse"] if rehearse else []), cwd=str(HERE))
    try:
        rc = child.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        child.kill()
        child.wait()
        print(f"=== phase {phase}: killed after {timeout_s}s", flush=True)
        return None
    finally:
        # the checkpoint (params + optimizer state) and the replay spill's memmap are checked
        # in the child and far too large to carry back: keep the metric log, config and result
        for heavy in ("checkpoint", "memmap_buffer"):
            for path in (out_dir / phase).glob(f"**/{heavy}"):
                shutil.rmtree(path, ignore_errors=True)
    print(f"=== phase {phase}: exit code {rc}", flush=True)
    if rc != 0 or not result.exists():
        return None
    return json.loads(result.read_text())


def compare_first_updates(one: Dict[str, Any], four: Dict[str, Any]) -> bool:
    ok = True
    print("=== 1-chip vs 4-chip first-update losses (rtol %.0e)" % RTOL_FOREIGN, flush=True)
    for name, a in one["first_update_losses"].items():
        b = four["first_update_losses"][name]
        # the policy loss of a first update is ~1e-4 (entropy-scale terms that cancel): give
        # near-zero metrics the absolute floor the golden harness gives cancellation-prone ones
        close = math.isfinite(a) and math.isfinite(b) and abs(a - b) <= RTOL_FOREIGN * abs(a) + 1e-4
        ok &= close
        print(f"  [{'ok' if close else 'FAIL'}] {name}: {a:.6g} vs {b:.6g}", flush=True)
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--out", type=Path, default=HERE / "chiprun_out" / "chip_smoke", help="every output goes under here")
    ap.add_argument("--rehearse", action="store_true", help="tiny widths on whatever JAX finds; proves nothing about the chip")
    ap.add_argument("--child", help=argparse.SUPPRESS)
    args = ap.parse_args()
    out_dir = args.out.resolve()
    if args.child:
        return run_child(args.child, out_dir, args.rehearse)

    out_dir.mkdir(parents=True, exist_ok=True)
    phases = ["dv3_1chip", "dv3_4chip"] if args.chips == 4 else ["dv3", "anakin"]
    limits = {"dv3": 840, "anakin": 300, "dv3_1chip": 540, "dv3_4chip": 600}
    ok, device, results = True, None, {}
    for phase in phases:
        res = run_phase(phase, out_dir, args.rehearse, limits[phase])
        if res is None:
            ok = False
            break
        results[phase], device = res, res["device"]
    if ok and args.chips == 4:
        ok = compare_first_updates(results["dv3_1chip"], results["dv3_4chip"])
        ok &= device["count"] == 4
    last = {"ok": bool(ok), "device": device}
    if args.rehearse:
        last["rehearsal"] = True
    print(json.dumps(last), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
