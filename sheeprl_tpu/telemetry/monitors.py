"""The process-global subsystem monitors, owned by the telemetry hub.

These classes grew up in ``utils/profiler.py`` as three independent
ad-hoc globals; the telemetry subsystem absorbs them behind the hub's one
registration API and one flush contract.  ``utils.profiler`` still
re-exports ``COMPILE_MONITOR`` / ``CHECKPOINT_MONITOR`` /
``RESILIENCE_MONITOR`` as thin shims (they are the SAME objects), so
every existing call site and test keeps working unchanged.

New here vs the profiler era: notable state transitions (injected faults,
watchdog stalls, env restarts, breaker opens, quarantines, checkpoint
saves, compiles) also land in the flight recorder, so a postmortem can
reconstruct the last minutes of a dead run from one file.
"""

from __future__ import annotations

import os
import sys
import threading
from typing import Any, Dict, List, Optional, Tuple

from sheeprl_tpu.telemetry.hub import HUB
from sheeprl_tpu.telemetry.recorder import RECORDER


class RecompileLimitExceeded(RuntimeError):
    """A compile-once function exceeded its allowed recompile budget."""


#: JAX's own event around every backend compile (or persistent-cache read)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

#: JAX's duration events of one compile -> the closed span record each becomes
COMPILE_RECORDS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    BACKEND_COMPILE_EVENT: "compile.backend",
}

#: JAX's plain event for an executable read from the persistent cache (on the
#: compiling thread, inside the backend-compile event it belongs to)
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

#: a backend compile this long is also one stderr line
COMPILE_LINE_S = 1.0

#: a trace event shorter than this is not written: one program traces some
#: 1,500 inner ``jit``s (every ``jnp`` function is one), nine in ten of them
#: under a millisecond and nested in a longer one
TRACE_FLOOR_S = 1e-3


class CompileMonitor:
    """Process-global per-function compile counter + abstract-signature log.

    ``count(name)`` is the number of executables built for ``name`` — the
    first compile is expected; every further one is a *recompile* caused by
    a new abstract signature.  The ``max_recompiles`` budget itself is
    enforced per-``AOTFunction`` instance (see ``parallel/compile.py``),
    which raises :class:`RecompileLimitExceeded`; this monitor is the
    process-wide aggregate view (metrics, dryrun stage summaries).

    ``fabric.compile`` programs are not all a run compiles: the replay
    ring's ``jax.jit`` scatter builds a program for every count of envs
    that finish at once.  :meth:`install` therefore also listens to JAX's
    own compile events and writes each into the span log as one closed
    record (``compile.trace``, ``compile.lower``, ``compile.backend``: the
    one place, ``fabric.compile`` programs and plain ``jax.jit`` ones
    alike).  A backend compile is also counted
    (``Compile/backend_compiles``), says whether the persistent cache was
    hit (``cache_hit``), and is a ``compile.backend`` recorder event naming
    the span open on the compiling thread and its loop iteration — which
    is how an operator learns which step recompiled, and whether a slow
    start was a cold cache.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._local = threading.local()  # cache_hit: set by the cache's event, taken by the compile's
        self._stats: Dict[str, Dict[str, Any]] = {}
        self._backend = [0, 0.0]  # count, seconds
        self._backend_flushed = 0  # the count the last rolling flush logged
        self._installed = False

    # -- JAX's own compile event ---------------------------------------------
    def install(self) -> None:
        """Listen to JAX's compile event (idempotent; ``telemetry.setup_run``)."""
        with self._lock:
            if self._installed:
                return
            self._installed = True
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_jax_event)
        jax.monitoring.register_event_listener(self._on_jax_mark)

    def _on_jax_mark(self, event: str, **_: Any) -> None:
        if event == CACHE_HIT_EVENT:
            self._local.cache_hit = True

    def _on_jax_event(self, event: str, duration: float, **fields: Any) -> None:
        name = COMPILE_RECORDS.get(event)
        if name is None:
            return
        from sheeprl_tpu.telemetry.spans import SPANS

        if event != BACKEND_COMPILE_EVENT:
            if duration >= TRACE_FLOOR_S or name != "compile.trace":
                SPANS.closed(name, duration)
            return
        hit = int(getattr(self._local, "cache_hit", False))
        self._local.cache_hit = False
        SPANS.closed(name, duration, {"cache_hit": hit})
        with self._lock:
            self._backend[0] += 1
            self._backend[1] += float(duration)
        under = SPANS.current()
        RECORDER.record(
            "compile.backend",
            seconds=round(float(duration), 3),
            span=under.name if under is not None else None,
            iteration=under.iteration if under is not None else None,
            cache_hit=hit,
        )
        if duration >= COMPILE_LINE_S:
            what = under.name if under is not None else fields.get("fun_name", "?")
            print(f"{name} {what} {duration:.1f} s {'hit' if hit else 'miss'}", file=sys.stderr, flush=True)

    def backend_totals(self) -> Tuple[int, float]:
        """(compile events seen, their seconds): every program, jitted or AOT."""
        with self._lock:
            return int(self._backend[0]), float(self._backend[1])

    # -- recording (called by parallel.compile.AOTFunction) -----------------
    def begin(self, name: str, signature: Any) -> None:
        """Count one compile of ``name`` in the process-global accounting.

        Pure bookkeeping: the ``max_recompiles`` budget is enforced
        per-:class:`~sheeprl_tpu.parallel.compile.AOTFunction` *instance*
        (each instance IS one compile-once program).  The global per-name
        count would otherwise aggregate across unrelated instances that
        happen to share a name — e.g. every run constructed in the same
        test process — and trip the budget for compiles the current
        program never performed.
        """
        with self._lock:
            st = self._stats.setdefault(
                name, {"count": 0, "seconds": 0.0, "signatures": []}
            )
            st["count"] += 1
            st["signatures"].append(str(signature))

    def abort(self, name: str, signature: Any = None) -> None:
        """Roll back one ``begin`` for ``name``: the compile failed, so no
        executable exists — counters must reflect programs actually built.
        When ``signature`` is given, the MATCHING history entry (searched
        from the end) is removed rather than blindly the last one, since two
        signatures of one function can compile concurrently."""
        with self._lock:
            st = self._stats.get(name)
            if st is None or st["count"] <= 0:
                return
            st["count"] -= 1
            if not st["signatures"]:
                return
            if signature is None:
                st["signatures"].pop()
                return
            sig_str = str(signature)
            for i in range(len(st["signatures"]) - 1, -1, -1):
                if st["signatures"][i] == sig_str:
                    del st["signatures"][i]
                    break

    def end(self, name: str, seconds: float) -> None:
        with self._lock:
            st = self._stats.get(name)
            if st is not None:
                st["seconds"] += float(seconds)

    @staticmethod
    def default_limit() -> Optional[int]:
        raw = os.environ.get("SHEEPRL_MAX_RECOMPILES", "").strip()
        return int(raw) if raw else None

    # -- queries -------------------------------------------------------------
    def count(self, name: str) -> int:
        with self._lock:
            return int(self._stats.get(name, {}).get("count", 0))

    def signatures(self, name: str) -> List[str]:
        with self._lock:
            return list(self._stats.get(name, {}).get("signatures", ()))

    def totals(self) -> Tuple[int, float]:
        """(total executables compiled, total compile seconds)."""
        with self._lock:
            return (
                sum(st["count"] for st in self._stats.values()),
                sum(st["seconds"] for st in self._stats.values()),
            )

    def summary(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            return {
                name: {
                    "count": st["count"],
                    "seconds": round(st["seconds"], 3),
                    "signatures": list(st["signatures"]),
                }
                for name, st in self._stats.items()
            }

    def compile_metrics(self) -> Dict[str, float]:
        """Aggregate counters for the hub flush (see metric.flush_metrics)."""
        count, seconds = self.totals()
        backend, backend_s = self.backend_totals()
        out: Dict[str, float] = {}
        if count:
            out["Compile/executables"] = float(count)
            out["Compile/compile_time_s"] = round(seconds, 3)
        if backend != self._backend_flushed:
            # only while it moves: a steady run pays no scalar for it
            out["Compile/backend_compiles"] = float(backend)
            out["Compile/backend_compile_time_s"] = round(backend_s, 3)
        return out

    def roll(self) -> None:
        """The hub's roll hook: the backend count now logged."""
        with self._lock:
            self._backend_flushed = self._backend[0]

    # hub-source alias: the hub polls ``metrics()`` on registered objects
    metrics = compile_metrics

    def reset(self) -> None:
        with self._lock:
            self._stats.clear()
            self._backend = [0, 0.0]
            self._backend_flushed = 0


#: The process-global monitor every AOTFunction reports into.
COMPILE_MONITOR = CompileMonitor()


class CheckpointMonitor:
    """Process-global accounting for the checkpointing subsystem
    (``sheeprl_tpu.checkpoint``) — the same pattern as
    :class:`CompileMonitor`: writer threads record, the telemetry hub
    surfaces the counters as ``Checkpoint/*`` without the loops threading a
    handle through."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self._saves = 0
            self._async_saves = 0
            self._errors = 0
            self._bytes_total = 0
            self._seconds_total = 0.0
            self._last_seconds = 0.0
            self._last_bytes = 0
            self._max_depth = 0

    def record_save(self, seconds: float, nbytes: int, asynchronous: bool) -> None:
        with self._lock:
            self._saves += 1
            self._async_saves += 1 if asynchronous else 0
            self._bytes_total += int(nbytes)
            self._seconds_total += float(seconds)
            self._last_seconds = float(seconds)
            self._last_bytes = int(nbytes)
        RECORDER.record(
            "ckpt.save",
            seconds=round(float(seconds), 4),
            bytes=int(nbytes),
            asynchronous=bool(asynchronous),
        )

    def record_error(self) -> None:
        with self._lock:
            self._errors += 1
        RECORDER.record("ckpt.error")

    def record_depth(self, depth: int) -> None:
        with self._lock:
            self._max_depth = max(self._max_depth, int(depth))

    def metrics(self) -> Dict[str, float]:
        """``Checkpoint/save_s`` is the LAST save's wall time — for async
        saves that is writer-thread time overlapped with training, i.e. the
        cost a synchronous save would have put on the critical path."""
        with self._lock:
            if self._saves == 0:
                return {}
            return {
                "Checkpoint/save_s": round(self._last_seconds, 4),
                "Checkpoint/bytes": float(self._last_bytes),
                "Checkpoint/total_saves": float(self._saves),
                "Checkpoint/total_bytes": float(self._bytes_total),
                "Checkpoint/queue_depth_max": float(self._max_depth),
            }

    def totals(self) -> Dict[str, float]:
        with self._lock:
            return {
                "saves": self._saves,
                "async_saves": self._async_saves,
                "errors": self._errors,
                "bytes": self._bytes_total,
                "seconds": round(self._seconds_total, 4),
            }


#: The process-global monitor the checkpoint writer reports into.
CHECKPOINT_MONITOR = CheckpointMonitor()


class ResilienceMonitor:
    """Process-global accounting for the resilience subsystem
    (``sheeprl_tpu.resilience``) — retries, watchdog stalls, env restarts,
    circuit-breaker transitions, quarantined snapshots, injected faults.
    Same pattern as the other monitors: primitives record from any thread,
    the telemetry hub surfaces the counters as ``Resilience/*``.

    When nothing has been recorded, :meth:`metrics` returns ``{}`` — a run
    with fault injection disabled and no recoveries emits NO ``Resilience/*``
    metrics at all (part of the zero-overhead-when-disabled gate)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self._retries = 0
            self._retry_successes = 0
            self._giveups = 0
            self._stalls = 0
            self._env_restarts = 0
            self._breaker_opens = 0
            self._quarantined = 0
            self._injected = 0
            self._injected_by_site: Dict[str, int] = {}

    def record_retry(self, site: str = "") -> None:
        with self._lock:
            self._retries += 1

    def record_retry_success(self, site: str = "") -> None:
        with self._lock:
            self._retry_successes += 1

    def record_giveup(self, site: str = "") -> None:
        with self._lock:
            self._giveups += 1
        RECORDER.record("retry.giveup", site=site)

    def record_stall(self, name: str = "") -> None:
        with self._lock:
            self._stalls += 1
        RECORDER.record("watchdog.stall", name=name)

    def record_env_restart(self, count: int = 1) -> None:
        with self._lock:
            self._env_restarts += int(count)
        RECORDER.record("env.restart", envs=int(count))

    def record_breaker(self, name: str, state: str) -> None:
        if state == "open":
            with self._lock:
                self._breaker_opens += 1
            RECORDER.record("breaker.open", name=name)

    def record_quarantine(self, path: Any = None) -> None:
        with self._lock:
            self._quarantined += 1
        RECORDER.record("ckpt.quarantine", path=str(path) if path is not None else None)

    def record_injection(self, site: str, kind: str) -> None:
        with self._lock:
            self._injected += 1
            self._injected_by_site[site] = self._injected_by_site.get(site, 0) + 1
        RECORDER.record("fault.injected", site=site, fault=kind)

    def metrics(self) -> Dict[str, float]:
        with self._lock:
            out: Dict[str, float] = {}
            if self._retries:
                out["Resilience/retries"] = float(self._retries)
            if self._retry_successes:
                out["Resilience/retry_successes"] = float(self._retry_successes)
            if self._giveups:
                out["Resilience/giveups"] = float(self._giveups)
            if self._stalls:
                out["Resilience/watchdog_stalls"] = float(self._stalls)
            if self._env_restarts:
                out["Resilience/env_restarts"] = float(self._env_restarts)
            if self._breaker_opens:
                out["Resilience/breaker_opens"] = float(self._breaker_opens)
            if self._quarantined:
                out["Resilience/quarantined_snapshots"] = float(self._quarantined)
            if self._injected:
                out["Resilience/faults_injected"] = float(self._injected)
            return out

    def totals(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "retries": self._retries,
                "retry_successes": self._retry_successes,
                "giveups": self._giveups,
                "stalls": self._stalls,
                "env_restarts": self._env_restarts,
                "breaker_opens": self._breaker_opens,
                "quarantined": self._quarantined,
                "injected": self._injected,
                "injected_by_site": dict(self._injected_by_site),
            }


#: The process-global monitor every resilience primitive reports into.
RESILIENCE_MONITOR = ResilienceMonitor()


# absorbed behind the hub's one registration API / one flush contract
HUB.register("compile", COMPILE_MONITOR.compile_metrics, on_roll=COMPILE_MONITOR.roll)
HUB.register("checkpoint", CHECKPOINT_MONITOR.metrics)
HUB.register("resilience", RESILIENCE_MONITOR.metrics)
