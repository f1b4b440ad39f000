"""One cell, once: probes around ``sheeprl_tpu.cli.run``, a fenced window, the result line.

The harness knows no algorithm.  What belongs to one configuration, one traffic mix, one
program path or one per-layer metric sits in a file of its own, found by name:

    configs/<config>.json     overrides, source, reduced/assumed sizes, which program path
    traffic/<traffic>.json    the overrides that make the traffic (env, env count, cadence)
    programs/<program>.py     names of the probed calls, work per call, what `correct` captures
    reference/<config>.py     the plain float32 reference
    metrics/<metric>.py       one reader per per-layer metric
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence

from chipbench import trace as trace_mod
from chipbench.window import Window

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
SCRATCH = ROOT / ".chipbench"  # logs, traces; listed in .gitignore
WARMUP_DISPATCHES = 3  # of the steady executable; they are also the steps `correct` follows
TRACE_FOR_S = 3.0  # the profiler records this long, after the window has closed


class WindowClosed(BaseException):
    """Leaves ``cli.run`` at the boundary that closes the window; only the harness catches it."""


class NoAccelerator(RuntimeError):
    pass


def load_module(kind: str, name: str):
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench.{kind}.{name.replace('.', '_')}", path)
    if spec.name in sys.modules:  # once: a module loaded anew would trace and compile its programs anew
        return sys.modules[spec.name]
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def load_cell(workload: str) -> Dict[str, Any]:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return {
        "bench": bench,
        "cell": cell,
        "config": json.loads((ROOT / cfg_entry["file"]).read_text()),
        "traffic": json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text()),
    }


T0_ENV = "CHIPBENCH_T0"  # when the first process started: set-up counts from there


def start_process(workload: str) -> float:
    """Sees to it that this process runs with the environment the cell's configuration states
    (``process_env`` in its file: settings a C library reads at start-up only, so they cannot be
    made from inside).  Where they are not set, it sets them and starts the same command again
    in this process's place, and does not return.  Returns the wall-clock time the run started at."""
    env = load_cell(workload)["config"].get("process_env", {}).get("set", {})
    if all(os.environ.get(k) == v for k, v in env.items()):
        return float(os.environ.pop(T0_ENV, time.time()))
    os.environ.update(env)
    os.environ[T0_ENV] = repr(time.time())
    sys.stdout.flush()
    os.execv(sys.executable, [sys.executable] + sys.argv)
    raise AssertionError("unreachable")


def metric_names(bench: Dict[str, Any], workload: str, kind: str) -> List[str]:
    return [m["name"] for m in bench[kind] if workload in m.get("workloads", [workload])]


class Call:
    """One probed call inside the window: host clock at entry and return (fenced in a traced run).
    ``device`` is true for a ``fabric.compile`` executable and for a host function that dispatches device
    work of a layer of its own (the ring write); false for the loop's own host work (the env step)."""

    __slots__ = ("name", "t0", "t1", "device", "work")

    def __init__(self, name: str, t0: float, t1: float, device: bool, work: Dict[str, int]):
        self.name, self.t0, self.t1, self.device, self.work = name, t0, t1, device, work


class Harness:
    def __init__(
        self,
        workload: str,
        seed: int,
        seconds: float,
        trace: bool,
        rehearse: bool = False,
        sabotage: Optional[Callable[[str, Any], Any]] = None,
        t_start: Optional[float] = None,
    ):
        self.t_start = time.time() if t_start is None else t_start  # wall clock: it may come from the process that started this one
        self.workload, self.seed, self.trace, self.rehearse = workload, int(seed), bool(trace), rehearse
        self.spec = load_cell(workload)
        self.program = load_module("programs", self.spec["config"]["program"])
        self.sabotage = sabotage
        self.window = Window(seconds, time.perf_counter)
        self.calls: List[Call] = []
        self.steady = 0
        self.snap: Dict[str, Any] = {"inputs": [], "outputs": []}
        self.last_out: Any = None
        self.timeline: Dict[str, float] = {}  # seconds from process start to the first time each thing happened
        self.compiles = 0  # executables built or fetched from the cache, by JAX's own event
        self.compiles_at_open: Optional[int] = None
        self.compiles_in_window = 0
        self.setup_s: Optional[float] = None
        self.tracing = False
        self.trace_dir = SCRATCH / "trace" / f"{workload}-{self.seed}"
        self.trace_result: Optional[Dict[str, Any]] = None
        self._iter_mark: Any = None
        self.cfg: Any = None
        self.executables: Dict[str, Any] = {}

    # ------------------------------------------------------------------
    def overrides(self) -> List[str]:
        cfg, traffic = self.spec["config"], self.spec["traffic"]
        over = list(cfg["overrides"]) + list(traffic["overrides"])
        if self.rehearse:
            over += list(cfg.get("rehearse_overrides", [])) + list(traffic.get("rehearse_overrides", []))
            over += ["fabric.accelerator=cpu"]
        else:
            over += ["fabric.accelerator=tpu", f"fabric.devices={self.spec['cell']['chips']}"]
        log_dir = SCRATCH / "logs" / f"{self.workload}-{self.seed}"
        shutil.rmtree(log_dir, ignore_errors=True)
        return over + [f"seed={self.seed % (2**31 - 1)}", f"log_dir={log_dir}", "print_config=False"]

    # -- probes ----------------------------------------------------------
    def install(self) -> None:
        import jax

        from sheeprl_tpu.parallel import fabric as fabric_mod
        from sheeprl_tpu.utils import profiler as profiler_mod

        harness = self
        real_compile = fabric_mod.Fabric.compile

        def probed_compile(fabric, fn, **kwargs):
            aot = real_compile(fabric, fn, **kwargs)
            if harness.sabotage is not None:
                aot = harness.sabotage(getattr(aot, "name", ""), aot) or aot
            return ProgramProbe(harness, aot)

        def probed_step(gate, update):
            harness.on_boundary()

        def on_event(event: str, duration: float, **_: Any) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                harness.compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)
        self._listener = on_event
        self._undo = [
            (fabric_mod.Fabric, "compile", real_compile),
            (profiler_mod.ProfilerGate, "step", profiler_mod.ProfilerGate.step),
        ]
        fabric_mod.Fabric.compile = probed_compile
        profiler_mod.ProfilerGate.step = probed_step
        for dotted, label in self.program.HOST_PROBES.items():
            module_name, cls_name, attr = dotted.rsplit(".", 2)
            owner = getattr(importlib.import_module(module_name), cls_name)
            real = getattr(owner, attr)
            self._undo.append((owner, attr, real))
            setattr(owner, attr, _probed_function(self, label, real))
        self._jax = jax

    def uninstall(self) -> None:
        for owner, attr, real in reversed(self._undo):
            setattr(owner, attr, real)
        self._jax.monitoring.unregister_event_duration_listener(self._listener)

    # -- the clockwork -----------------------------------------------------
    def fence(self) -> None:
        if self.last_out is not None:
            self._jax.block_until_ready(self.last_out)

    def on_boundary(self) -> None:
        """Called at the top of every loop iteration (the loops' ``ProfilerGate.step``)."""
        if self.window.t_open is None:
            self.timeline.setdefault("first_iteration", time.time() - self.t_start)
            if self.steady >= WARMUP_DISPATCHES:
                self.program.before_window(self.snap)  # warms what the traffic may still meet for the first time
                self.fence()
                self.compiles_at_open = self.compiles
                self.calls.clear()
                self.setup_s = time.time() - self.t_start
                self.window.open()
            return
        if self.window.is_open:
            due = self.window.boundary()
            self.window.add(**self.program.work_per_iteration(self.cfg))
            if not due:
                return
            self.fence()
            self.window.close()
            self.memory_at_close = [d.memory_stats() or {} for d in self._jax.devices()[: int(self.spec["cell"]["chips"])]]
            self.compiles_in_window = self.compiles - self.compiles_at_open
            if not self.trace:
                raise WindowClosed()
            # the traced stretch follows the window, so that starting, stopping and reading the
            # profiler cost the window nothing; its calls run unfenced, as in an untraced run
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            self.trace_dir.mkdir(parents=True)
            self._jax.profiler.start_trace(str(self.trace_dir))
            self.tracing, self._trace_t0 = True, time.perf_counter()
        else:
            self._iter_mark.__exit__(None, None, None)
            if time.perf_counter() - self._trace_t0 >= TRACE_FOR_S:
                self.fence()
                self._jax.profiler.stop_trace()
                self.tracing = False
                raise WindowClosed()
        self._iter_mark = self._jax.profiler.TraceAnnotation(trace_mod.ITER)
        self._iter_mark.__enter__()

    def annotation(self, label: str):
        """A host span in the profiler's own trace while it records, nothing otherwise."""
        if self.tracing:
            return self._jax.profiler.TraceAnnotation(trace_mod.MARK + label)
        return contextlib.nullcontext()

    def read_trace(self) -> None:
        """After the run: the recorded trace reduced to busy time, top operations and labelled gaps."""
        path = trace_mod.find_xplane(str(self.trace_dir))
        self.trace_result = trace_mod.reduce(trace_mod.read_xplane(path)) if path else None
        shutil.rmtree(self.trace_dir, ignore_errors=True)


def _probed_function(harness: Harness, label: str, real: Callable) -> Callable:
    def probed(*args, **kwargs):
        if harness.tracing:
            with harness.annotation(label):
                return real(*args, **kwargs)
        if not harness.window.is_open:
            if harness.steady < WARMUP_DISPATCHES:
                harness.program.observe(label, args, kwargs, None, harness.snap)
            return real(*args, **kwargs)
        on_device = label in harness.program.DEVICE_CALLS
        t0 = time.perf_counter()
        out = real(*args, **kwargs)
        if harness.trace and on_device:  # a traced run fences every call of its window
            harness._jax.block_until_ready(harness.program.device_result(label, args, out))
        harness.calls.append(Call(label, t0, time.perf_counter(), on_device, {}))
        return out

    return probed


class ProgramProbe:
    """Stands in for one ``fabric.compile`` program; everything else is the real ``AOTFunction``."""

    def __init__(self, harness: Harness, aot: Any) -> None:
        self.harness, self.aot = harness, aot
        self.name = getattr(aot, "name", getattr(aot, "__name__", "program"))
        harness.executables[self.name] = aot

    def __getattr__(self, name: str) -> Any:
        return getattr(self.aot, name)

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        h = self.harness
        jax = h._jax
        if any(isinstance(x, jax.core.Tracer) for x in jax.tree.leaves((args, kwargs))):
            return self.aot(*args, **kwargs)  # inlined into another program
        steady = self.name == h.program.STEADY and h.program.is_steady(h.cfg, args, kwargs)
        if steady and h.steady < WARMUP_DISPATCHES:
            # `correct` follows the first steps of the one object the window then drives
            h.snap["inputs"].append(jax.device_get(h.program.capture_inputs(args, kwargs, h.steady)))
        t0 = time.perf_counter()
        h.timeline.setdefault(self.name, time.time() - h.t_start)
        with h.annotation(self.name):
            out = self.aot(*args, **kwargs)
        if h.trace and h.window.is_open:  # a traced run fences every call of its window
            jax.block_until_ready(out)
        t1 = time.perf_counter()
        h.last_out = out
        if steady:
            h.steady += 1
            if h.steady <= WARMUP_DISPATCHES:
                h.snap["outputs"].append(jax.device_get(h.program.capture_outputs(out)))
        elif h.steady < WARMUP_DISPATCHES:
            h.program.observe(self.name, args, kwargs, out, h.snap)
        if h.window.is_open:
            work = h.program.work_per_call(h.cfg, self.name, args, kwargs)
            h.window.add(**work)
            h.calls.append(Call(self.name, t0, t1, True, work))
        return out


# ----------------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------------

def device_info(jax) -> Dict[str, Any]:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def memory_report(harness: Harness) -> Dict[str, Any]:
    """The allocator's figures on the fullest chip as the window closed, beside XLA's analysis of what was dispatched."""
    stats = harness.memory_at_close
    report: Dict[str, Any] = {
        "peak_bytes_in_use": max((s.get("peak_bytes_in_use", 0) for s in stats), default=0),
        "bytes_in_use": max((s.get("bytes_in_use", 0) for s in stats), default=0),
        "bytes_limit": stats[0].get("bytes_limit") if stats else None,
        "programs": {},
    }
    for name, aot in harness.executables.items():
        for exe in list(getattr(aot, "_cache", {}).values()):
            try:
                ma = exe.memory_analysis()
            except Exception:  # noqa: BLE001  (a fallback marker or a backend without the analysis)
                continue
            if ma is None:
                continue
            report["programs"][name] = {
                "argument": int(ma.argument_size_in_bytes), "output": int(ma.output_size_in_bytes),
                "temp": int(ma.temp_size_in_bytes), "alias": int(ma.alias_size_in_bytes),
            }
    return report


def memory_peak_bytes(memory: Dict[str, Any]) -> int:
    """The peak on the fullest chip.  The allocator's ``peak_bytes_in_use`` leaves a running program's
    temporaries out on this runtime (PERF.md, memory finding), so the peak is what was resident when the
    window closed plus the temporaries of the largest executable the run dispatched, by XLA's own
    analysis of that executable; the allocator's figure where that is larger."""
    temp = max((p["temp"] for p in memory["programs"].values()), default=0)
    return int(max(memory["peak_bytes_in_use"], memory["bytes_in_use"] + temp))


def judge(program, cfg, snap, config_file, compiles_in_window: int):
    """``correct`` from what the probes copied: the reference follows the captured dispatches, every number
    compared stands beside its limit, and a compile inside the window fails the run."""
    t_check = time.perf_counter()
    numbers = program.check(cfg, snap, config_file)
    check_s = time.perf_counter() - t_check
    compared = {k: {"value": v["value"], "limit": v["limit"]} for k, v in numbers.items() if "limit" in v}
    correct = bool(compared) and all(v["value"] <= v["limit"] for v in compared.values())  # a nan fails
    compared["compiles_in_window"] = {"value": compiles_in_window, "limit": 0}
    return correct and compiles_in_window == 0, compared, numbers, check_s


def run_cell(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    rehearse: bool = False,
    sabotage: Optional[Callable[[str, Any], Any]] = None,
    t_start: Optional[float] = None,
    stand_ins: Sequence[str] = (),
) -> Dict[str, Any]:
    """Run one cell once and return the result (``main`` prints it as the last line).

    ``stand_ins`` (``calibrate.py`` and the tests, never a run of the benchmark) names what is then put in the
    program's place and judged by the same ``judge``: ``control``, the reference in the precision below the
    configuration's, or a planted fault of the reference.  Each must come out as not correct."""
    h = Harness(workload, seed, seconds, trace, rehearse, sabotage, t_start)
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    import jax

    if not cache_dir:  # a fixed path inside the checkout: the path is part of the cache's key
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    device = device_info(jax)
    chips = int(h.spec["cell"]["chips"])
    if rehearse:
        if os.environ.get("JAX_PLATFORMS", "") != "cpu" or device["platform"] != "cpu":
            raise NoAccelerator("--rehearse runs only where the caller set JAX_PLATFORMS=cpu")
    elif device["platform"] != "tpu" or device["count"] < chips:
        raise NoAccelerator(f"{workload} needs {chips} TPU chip(s); JAX found {device}")

    from sheeprl_tpu.cli import run
    from sheeprl_tpu.config.compose import compose

    overrides = h.overrides()
    h.cfg = compose(overrides).as_dict()
    h.install()
    try:
        run(overrides)
        raise RuntimeError("cli.run returned before the window closed: algo.total_steps was reached")
    except WindowClosed:
        pass
    finally:
        h.uninstall()

    end_to_end = dict(h.window.metrics(), setup_s=h.setup_s)
    memory = memory_report(h)
    if trace:
        h.read_trace()
    params_shapes = h.program.param_shapes(h.snap["inputs"][0])
    h.last_out = None
    h.executables.clear()
    gc.collect()
    shutil.rmtree(SCRATCH / "logs" / f"{workload}-{h.seed}", ignore_errors=True)

    # ---- the result line --------------------------------------------------
    bench = h.spec["bench"]
    peaks = json.loads((HERE / "peaks.json").read_text())
    if device["kind"] not in peaks and not rehearse:
        raise KeyError(f"no peaks on record for device_kind {device['kind']!r}: add it to chipbench/peaks.json")
    ctx = {
        "window": h.window, "calls": h.calls, "cfg": h.cfg, "trace": h.trace_result, "chips": chips,
        "peak": peaks.get(device["kind"]), "program": h.program, "param_shapes": params_shapes,
    }
    metrics: Dict[str, Dict[str, Any]] = {}
    units = {m["name"]: m["unit"] for m in bench["end_to_end"] + bench["per_layer"]}
    if trace:
        for name in metric_names(bench, workload, "per_layer"):
            value = load_module("metrics", name).read(ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": units[name]}
    else:
        for name in metric_names(bench, workload, "end_to_end"):
            metrics[name] = {"value": end_to_end[name], "unit": units[name]}

    device["memory_peak_bytes"] = memory_peak_bytes(memory)
    if trace and h.trace_result is not None:
        device["busy_s"], device["window_s"] = h.trace_result["busy_s"], h.trace_result["window_s"]

    print(f"window closed: {json.dumps(end_to_end)} memory: {json.dumps(memory)}", file=sys.stderr, flush=True)

    # ---- correct: once the window has closed, the peak is read and the state is freed ----
    correct, compared, numbers, check_s = judge(h.program, h.cfg, h.snap, h.spec["config"], h.compiles_in_window)

    result: Dict[str, Any] = {
        "correct": correct,
        "attempted": len(h.window.boundaries) - 1,
        "failed": 0,
        "metrics": metrics,
        "device": device,
    }
    if trace and h.trace_result is not None:
        result["breakdown"] = {k: h.trace_result[k] for k in ("device_ops", "idle_gaps")}
    result["detail"] = {
        "workload": workload, "seed": seed, "process_env": h.spec["config"].get("process_env", {}).get("set", {}),
        "window_s": h.window.elapsed, "iterations": len(h.window.boundaries) - 1,
        "env_steps": h.window.env_steps, "updates": h.window.updates, "check_s": check_s, "memory": memory,
        "setup_timeline": h.timeline, "iteration_ms": [round(x, 1) for x in h.window.iteration_ms()],
        "calls_ms": {n: [round((c.t1 - c.t0) * 1e3, 1) for c in h.calls if c.name == n] for n in sorted({c.name for c in h.calls})},
        "end_to_end": end_to_end if trace else None,
        "extra": {k: v for k, v in numbers.items() if "limit" not in v},
    }
    for name in stand_ins:
        other = h.program.stand_in(h.cfg, h.snap, h.spec["config"], name)
        o_correct, o_compared, o_numbers, o_s = judge(h.program, h.cfg, other, h.spec["config"], h.compiles_in_window)
        result.setdefault("stand_ins", {})[name] = {
            "correct": o_correct, "compared": o_compared, "seconds": o_s,
            "extra": {k: v for k, v in o_numbers.items() if "limit" not in v},
        }
    result["compared"] = compared
    return result
