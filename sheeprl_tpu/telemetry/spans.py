"""Step-phase spans: nestable host-side timing, one record per boundary.

The named timers (``utils/timer.py``) answer "how long did phase X take";
they cannot answer "what FRACTION of the window went where" — the number
that decides whether to tune ``traj_queue_slots`` (queue waits dominate)
or shard the model further (update dispatch dominates).  The span tracker
keeps a per-thread stack of open spans and does three things with every
push/pop pair:

* attributes the span its EXCLUSIVE time (children subtracted) and
  aggregates a rolling window into phase-breakdown fractions that sum to
  ~1.0 (an ``other`` bucket absorbs untracked host time): ``Phase/*``,
  ``/v1/phase``, the postmortem's ``phase_breakdown``;
* appends one :class:`SpanRecord` to a bounded in-memory ring
  (:data:`RECORD_CAPACITY` records, untouched by ``roll_window``):
  ``name, start, end`` on ``time.perf_counter``, its own ``id``, its
  ``parent`` (the span open under it on the same thread, or the span that
  caused it across threads), the loop ``iteration`` it belongs to, the
  thread's name and an optional small dict of counts.
  ``SPANS.records()`` hands them to whoever wants the run's own account
  of an iteration (``chipbench/metrics/``);
* enters/exits a ``jax.profiler.TraceAnnotation`` of the span's name (the
  iteration span a ``StepTraceAnnotation`` with ``step_num=update``) that
  carries the record's ``id`` as its ``span`` stat, which
  costs next to nothing while no profiler session records and puts every
  host span on the device trace's clock while one does — a
  ``ProfilerGate`` window, a ``TRACER`` window or anybody's
  ``jax.profiler.start_trace`` alike.

Span taxonomy (docs/telemetry.md has the table with counts and parents):

* ``setup``            — the run's set-up: opened as the first thing
  ``cli.run`` does (:meth:`SpanTracker.begin_setup`), closed by the first
  ``iter`` (or the first phase) on that thread; counts ``pre_run_ms``, the
  process's age at ``cli.run``'s entry.  Its children ``setup.compose``,
  ``.register``, ``.fabric``, ``.logger``, ``.env``, ``.agent``,
  ``.optimizer``, ``.replay``, ``.resume``, ``.prefill`` (and ``.import``)
  are opened where that work is (:meth:`SpanTracker.setup_span`)
* ``compile.trace`` / ``compile.lower`` / ``compile.backend`` — one CLOSED
  record per JAX compile event (:meth:`SpanTracker.closed`, written by
  ``COMPILE_MONITOR``); ``compile.backend`` counts ``cache_hit``
* ``iter``             — one loop iteration (:meth:`SpanTracker.iteration`,
  called beside ``profiler.step(update)``); every span below that the
  loop's thread opens is its descendant and carries its ``iteration``
* ``rollout``          — env interaction / segment collection
* ``env.step``         — the vector env's ``step`` (``utils/env.vectorize``)
* ``exec.<program>``   — one dispatch of a ``fabric.compile`` program
  (``parallel/compile.py AOTFunction.__call__``)
* ``queue.wait``       — the learner blocked on the trajectory queue
* ``replay.write``     — host→ring staging of new rows
* ``update.dispatch``  — the train-phase device dispatch (fused on-device
  sampling included — it is part of the same executable)
* ``player.sync``      — ``PlayerSync.before_dispatch``/``after_dispatch``
* ``param.broadcast``  — learner→actor param publication
* ``log.flush`` / ``health.poll`` / ``ckpt.save`` — the loop's stalls:
  the metric flush, the sentinel's poll, the caller-thread part of a save
* ``ckpt.snapshot``    — checkpoint serialize+write (writer thread; its
  ``parent`` and ``iteration`` are those of the ``ckpt.save`` that queued it)
* ``pipeline.stage.<name>.fwd`` / ``.bwd`` — per-stage forward/backward
  wall time of the pipelined world-model update, opened by the
  standalone stage programs of ``parallel/pipeline.py
  compile_stage_pair`` (no caller in the tree: ROADMAP D5); inside the fused train
  phase the stages appear as ``pipeline.<name>`` ``named_scope``s in
  device traces instead (one dispatch = one ``update.dispatch`` span).
  The derived first-class metric is ``Pipeline/bubble_frac`` — the
  schedule's idle fraction ``(S-1)/(M+S-1)`` (docs/pipeline.md).

Wiring is centralized: ``utils.timer`` bridges the two phase timers every
loop already has (:data:`TIMER_PHASES`), and the compile / env / replay /
player-sync / checkpoint / metric / health layers open their own spans —
no per-loop copies beyond the one ``SPANS.iteration(update)`` line.

Two kinds of span, chosen where the span is opened.  A PHASE (the default:
``rollout``, ``update.dispatch``, ``replay.write``, ``queue.wait``,
``param.broadcast``, ``ckpt.snapshot``, ``pipeline.stage.*``) is what
``Phase/*`` is made of, and a phase with no phase open under it is
TOP-LEVEL: opening a top-level ``update.dispatch`` ticks the trace
scheduler (``tracer.py``), closing one feeds ``/healthz`` liveness, and
top-level phase edges are flight-recorder events.  A BOUNDARY
(``phase=False``: ``setup``, ``setup.*``, ``iter``, ``exec.*``, ``env.step``,
``player.sync``, ``stats.pull``, ``log.flush``, ``health.poll``,
``ckpt.save``) is a record
and a profiler annotation and nothing else: its time stays with the phase
it runs under (else ``Phase/other``), it makes no phase less top-level and
writes no recorder event — so ``Phase/*``, ``/v1/phase``, ``/healthz`` and
the postmortem read as they did before the boundaries existed.


Device attribution: dispatch is asynchronous, so a span's host time is
not its device time, and no span edge ever waits for the device — a trace
window records the pipeline as it runs.  The train phases carry
``jax.named_scope``s (docs/telemetry.md), which is how device time is
attributed (``python -m chipbench.scopes <trace dir>``, which keeps their
list); ``metric.sync_timers`` is the one fence knob left, on the bridged
phases.
"""

from __future__ import annotations

import itertools
import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Deque, Dict, List, NamedTuple, Optional, Tuple

from sheeprl_tpu.telemetry.hub import HUB
from sheeprl_tpu.telemetry.recorder import RECORDER
from sheeprl_tpu.telemetry.tracer import TRACER

#: timer-name → span-phase bridge (utils/timer.py opens these automatically,
#: which is what wires all 12 algo loops without touching them)
TIMER_PHASES: Dict[str, str] = {
    "Time/env_interaction_time": "rollout",
    "Time/train_time": "update.dispatch",
}

#: the iteration span: the frame every other span of a loop thread hangs under
ITER = "iter"

#: the set-up root: ``cli.run``'s entry to the loop's first iteration
SETUP = "setup"

#: closed spans kept in memory (a DV3 iteration closes about 20)
RECORD_CAPACITY = 32768

_now = time.perf_counter
_ids = itertools.count(1)


class SpanRecord(NamedTuple):
    """One closed span.  ``start``/``end`` are ``time.perf_counter`` seconds."""

    name: str
    start: float
    end: float
    id: int
    parent: Optional[int]
    iteration: Optional[int]
    thread: str
    counts: Optional[Dict[str, int]]


def process_age_ms() -> Optional[int]:
    """Milliseconds since the OS started this process (it survives an
    ``execv``: the interpreter, ``import jax`` and whatever the caller did
    before ``cli.run``).  None where the platform gives no start time."""
    try:
        with open("/proc/self/stat") as f:
            started = int(f.read().rsplit(")", 1)[1].split()[19])  # field 22: ticks after boot
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0, int((uptime - started / os.sysconf("SC_CLK_TCK")) * 1e3))
    except (OSError, ValueError, IndexError):
        return None


_marks: Optional[Tuple[Any, Any]] = None  # jax.profiler's two annotation classes, looked up once


def _annotate(name: str, span_id: int, step: Optional[int] = None) -> Any:
    """The profiler's own host annotation for one span (entered by the caller).
    Its ``span`` stat is the record's ``id``: what tells the program's spans
    from JAX's own marks on the same thread's line of a trace, and joins a
    trace to ``SPANS.records()``."""
    global _marks
    if _marks is None:
        from jax.profiler import StepTraceAnnotation, TraceAnnotation

        _marks = (TraceAnnotation, StepTraceAnnotation)
    if step is None:
        return _marks[0](name, span=span_id)
    return _marks[1](name, step_num=step, span=span_id)


class _Span:
    __slots__ = ("name", "start", "child_s", "id", "parent", "iteration", "counts", "phase", "up", "mark")

    def __init__(self, name, span_id, start, parent, iteration, counts, phase, up, mark) -> None:
        self.name = name
        self.start = start
        self.child_s = 0.0
        self.id = span_id
        self.parent = parent
        self.iteration = iteration
        self.counts = counts
        self.phase = phase  # False: a boundary (record and annotation only)
        self.up = up  # the innermost phase open under it on its thread (None: top-level)
        self.mark = mark

    def count(self, **counts: int) -> None:
        """Add to this span's counts (what it moved is often known only inside it)."""
        if self.counts is None:
            self.counts = {}
        for k, v in counts.items():
            self.counts[k] = self.counts.get(k, 0) + int(v)


class SpanTracker:
    """Process-global span stack (per-thread) + windowed phase aggregator
    + the bounded log of closed spans."""

    def __init__(self) -> None:
        self.enabled = True
        self._lock = threading.Lock()
        self._local = threading.local()
        self._excl: Dict[str, float] = {}
        self._counts: Dict[str, int] = {}
        self._records: Deque[SpanRecord] = deque(maxlen=RECORD_CAPACITY)
        self._stacks: Dict[int, Tuple[str, list]] = {}  # thread ident -> (name, its stack): the open-span view
        self._setup: Optional[_Span] = None  # the set-up root while it is open
        self._window_start = _now()
        # liveness signal for /healthz (introspect.py): wall time of the
        # newest COMPLETED top-level update.dispatch span + total count —
        # survives window rolls, so a stalled learner is visible however
        # long it has been wedged
        self._last_update_done: Optional[float] = None
        self._updates_done = 0

    # -- configuration -------------------------------------------------------
    def configure(self, cfg: Any = None) -> None:
        """Apply the ``telemetry.spans`` config group."""
        cfg = cfg or {}
        self.enabled = bool(cfg.get("enabled", True))
        if not self.enabled:
            self._discard_setup()

    # -- the span stack ------------------------------------------------------
    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
            with self._lock:
                self._stacks[threading.get_ident()] = (threading.current_thread().name, stack)
        return stack

    def push(
        self,
        name: str,
        counts: Optional[Dict[str, int]] = None,
        cause: Optional[_Span] = None,
        iteration: Optional[int] = None,
        phase: bool = True,
    ) -> Optional[_Span]:
        """Open a span; returns the token :meth:`pop` needs (None when
        disabled — pop of None is a no-op, so call sites stay branch-free).

        ``phase=False`` opens a boundary: a record and an annotation, no part
        of ``Phase/*``.  ``cause`` is what :meth:`current` returned on the
        thread that asked for this work: where nothing is open under the new
        span on its own thread, that span is its parent and gives it its
        iteration."""
        if not self.enabled:
            return None
        if phase and self._setup is not None:
            self.end_setup()  # a loop with no `iter` frame: its first phase ends set-up
        stack = self._stack()
        under = stack[-1] if stack else None
        origin = under if under is not None else cause
        parent, inherited = (origin.id, origin.iteration) if origin is not None else (None, None)
        up = under if under is None or under.phase else under.up
        if phase and up is None and name == "update.dispatch":
            # the update tick stream the trace scheduler counts on; before the
            # annotation, so a window this tick opens holds this dispatch
            TRACER.tick()
        if iteration is None:
            iteration = inherited
        span_id = next(_ids)
        mark = _annotate(name, span_id, iteration if name == ITER else None)
        mark.__enter__()
        span = _Span(name, span_id, _now(), parent, iteration, counts, phase, up, mark)
        stack.append(span)
        return span

    def pop(self, token: Optional[_Span]) -> None:
        """Close ``token`` (and any span opened under it that leaked — a
        raise between push and pop unwinds with the parent)."""
        if token is None:
            return
        stack = self._stack()
        end = _now()
        thread = threading.current_thread().name
        while stack:
            span = stack.pop()
            span.mark.__exit__(None, None, None)
            dur = max(0.0, end - span.start)
            top = span.phase and span.up is None
            if span.phase and span.up is not None:
                span.up.child_s += dur
            record = SpanRecord(
                span.name, span.start, end, span.id, span.parent, span.iteration, thread, span.counts
            )
            with self._lock:
                self._records.append(record)
                if span.phase:
                    self._excl[span.name] = self._excl.get(span.name, 0.0) + max(0.0, dur - span.child_s)
                    self._counts[span.name] = self._counts.get(span.name, 0) + 1
                if top and span.name == "update.dispatch":
                    self._last_update_done = time.time()
                    self._updates_done += 1
            if top:
                # top-level phase edges are flight-recorder events (bounded
                # ring — per-update cadence, not per-env-step)
                RECORDER.record("span", name=span.name, seconds=round(dur, 6))
            if span is token:
                return

    @contextmanager
    def span(self, name: str, phase: bool = True, **counts: int):
        token = self.push(name, counts or None, phase=phase)
        try:
            yield token
        finally:
            self.pop(token)

    def closed(self, name: str, seconds: float, counts: Optional[Dict[str, int]] = None) -> None:
        """One record of an interval that is already over (a compile, as JAX's
        own event reports it): it ends now and began ``seconds`` ago; parent,
        iteration and thread are those of the span open on this thread.  No
        profiler annotation, no part of ``Phase/*``.  Such intervals may nest
        (an inner ``jit`` traced inside an outer one): readers take their
        union, never their sum."""
        if not self.enabled:
            return
        end = _now()
        under = self.current()
        parent, iteration = (under.id, under.iteration) if under is not None else (None, None)
        record = SpanRecord(
            name, end - max(0.0, float(seconds)), end, next(_ids), parent, iteration,
            threading.current_thread().name, counts,
        )
        with self._lock:
            self._records.append(record)

    def depth(self) -> int:
        return len(self._stack())

    def current(self) -> Optional[_Span]:
        """The innermost span open on this thread (``name``, ``id``,
        ``iteration``): what a worker thread passes as ``cause`` for the work
        this span queued, and what a compile event is put down to."""
        stack = self._stack()
        return stack[-1] if stack else None

    # -- set-up --------------------------------------------------------------
    def begin_setup(self) -> None:
        """The first thing ``cli.run`` does: open the root boundary ``setup``
        on this thread.  It counts ``pre_run_ms`` (:func:`process_age_ms` at
        this call; left out where the platform gives none) and stays open
        until the loop's first :meth:`iteration` (or first phase), or
        ``telemetry.shutdown_run``.  A new run starts with the default knobs:
        ``setup_run`` applies its config later, from inside ``get_logger``,
        and a run that turns spans off drops what was opened here."""
        entered, age = _now(), process_age_ms()
        self._discard_setup()
        self.enabled = True
        first = "jax" not in sys.modules  # `python -m sheeprl_tpu`: the annotation below imports it
        root = self.push(SETUP, None if age is None else {"pre_run_ms": age}, phase=False)
        root.start = entered
        self._setup = root
        if first:
            self.closed("setup.import", _now() - entered)

    def end_setup(self) -> None:
        """Close ``setup`` (and whatever leaked under it) where this thread opened it."""
        root = self._setup
        if root is not None and root in self._stack():
            self._setup = None
            self.pop(root)
            self._announce(root)

    def _discard_setup(self) -> None:
        """Spans are off for this run: unwind ``setup`` and drop this run's records."""
        root, self._setup = self._setup, None
        stack = self._stack()
        if root is None or root not in stack:
            return
        while stack:
            span = stack.pop()
            span.mark.__exit__(None, None, None)
            if span is root:
                break
        with self._lock:
            kept = [r for r in self._records if r.id < root.id]
            self._records.clear()
            self._records.extend(kept)

    @contextmanager
    def setup_span(self, name: str):
        """A ``setup.*`` child, opened where the work is: a boundary whose
        close is also one flight-recorder event and one stderr line, so a
        slow start can be watched while it runs.  Nothing outside a run's
        set-up (the shared constructors also serve evaluation, the server and
        a loop that rebuilds an env mid-run)."""
        root = self._setup
        if root is None or root not in self._stack():
            yield None
            return
        token = self.push(name, phase=False)
        try:
            yield token
        finally:
            if token in self._stack():  # not where this run's config turned spans off meanwhile
                self.pop(token)
                self._announce(token)

    @staticmethod
    def _announce(span: _Span) -> None:
        seconds = _now() - span.start
        RECORDER.record("setup", name=span.name, seconds=round(seconds, 3))
        print(f"{span.name} {seconds:.2f} s", file=sys.stderr, flush=True)

    def open_spans(self) -> List[Dict[str, Any]]:
        """Every span open now, on any thread, oldest first, with its age:
        what a run that hangs or is killed was inside (``/v1/phase``, the
        postmortem)."""
        now, alive = _now(), {t.ident for t in threading.enumerate()}
        with self._lock:
            for ident in [i for i, (_, stack) in self._stacks.items() if not stack and i not in alive]:
                del self._stacks[ident]
            stacks = [(thread, list(stack)) for thread, stack in self._stacks.values()]
        spans = [(s, thread) for thread, stack in stacks for s in stack]
        return [
            {"name": s.name, "age_s": round(now - s.start, 3), "thread": thread, "iteration": s.iteration, "id": s.id}
            for s, thread in sorted(spans, key=lambda st: st[0].start)
        ]

    # -- the iteration frame -------------------------------------------------
    def iteration(self, update: int) -> None:
        """Top of a loop iteration: close the iteration span open on this
        thread (and whatever leaked under it) and open the one for ``update``.
        The first one of a run closes ``setup``."""
        if self._setup is not None:
            self.end_setup()
        self.end_iteration()
        self._local.iter = self.push(ITER, iteration=int(update), phase=False)

    def end_iteration(self) -> None:
        """After the loop (and from ``shutdown_run``, for a loop that raised)."""
        token = getattr(self._local, "iter", None)
        if token is not None:
            self._local.iter = None
            if token in self._stack():
                self.pop(token)

    # -- the span log --------------------------------------------------------
    def records(self) -> List[SpanRecord]:
        """Closed spans, oldest first.  Open spans are not in the log: a span
        that never closes (the loop left by an exception) never shows up."""
        with self._lock:
            return list(self._records)

    # -- liveness ------------------------------------------------------------
    def last_update_age_s(self) -> Optional[float]:
        """Seconds since the newest completed update dispatch (None before
        the first one — warm-up compiles can legitimately take many
        minutes, so pre-first-update runs are never called stalled)."""
        with self._lock:
            if self._last_update_done is None:
                return None
            return max(0.0, time.time() - self._last_update_done)

    @property
    def updates_done(self) -> int:
        with self._lock:
            return self._updates_done

    # -- window aggregation --------------------------------------------------
    def breakdown(self) -> Dict[str, Any]:
        """The current window's phase breakdown.

        Fractions are normalized against ``max(window wall, Σ exclusive)``:
        spans on concurrent threads (the checkpoint writer overlapping the
        learner) can legitimately sum past wall time, and the breakdown
        must still sum to ~1.0.  ``other_frac`` is the untracked remainder
        of the window wall."""
        with self._lock:
            excl = dict(self._excl)
            counts = dict(self._counts)
            window_s = max(_now() - self._window_start, 1e-9)
        tracked = sum(excl.values())
        total = max(window_s, tracked)
        phases = {
            name: {
                "seconds": round(s, 6),
                "frac": round(s / total, 6),
                "count": counts.get(name, 0),
            }
            for name, s in sorted(excl.items())
        }
        return {
            "window_s": round(window_s, 6),
            "phases": phases,
            "other_frac": round(max(0.0, window_s - tracked) / total, 6),
        }

    def metrics(self) -> Dict[str, float]:
        """``Phase/*`` fractions for the hub flush (empty when no span
        closed this window — a run with spans disabled emits nothing)."""
        bd = self.breakdown()
        if not bd["phases"]:
            return {}
        out = {f"Phase/{name}": p["frac"] for name, p in bd["phases"].items()}
        out["Phase/other"] = bd["other_frac"]
        return out

    def roll_window(self) -> None:
        """Start a fresh aggregation window (fired by the per-interval
        metric flush via the hub's ``on_roll`` hook).  The span log stays."""
        with self._lock:
            self._excl.clear()
            self._counts.clear()
            self._window_start = _now()

    def reset(self) -> None:
        """Tests: fresh window, empty log, default knobs, and this thread's
        stack closed (other threads' stacks drain as their context managers
        exit)."""
        stack = self._stack()
        while stack:
            stack.pop().mark.__exit__(None, None, None)
        self._local.iter = None
        self._setup = None
        self.roll_window()
        self.enabled = True
        with self._lock:
            self._records.clear()
            self._last_update_done = None
            self._updates_done = 0


#: The process-global span tracker.
SPANS = SpanTracker()

#: Module-level convenience: ``with span("queue.wait"): ...``
span = SPANS.span

HUB.register("spans", SPANS.metrics, on_roll=SPANS.roll_window)
