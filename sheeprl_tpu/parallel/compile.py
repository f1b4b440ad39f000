"""Compile-once execution layer: explicit AOT lowering + parallel warm-up.

Motivation (Podracer / RLAX TPU recipe): an RL framework's device programs
should be **compiled once, then only fed data**.  Implicit ``jax.jit``
first-call tracing hides when that contract breaks — a last-batch
remainder, a framestack variant or a drifted scalar dtype silently
re-traces a multi-minute TPU program mid-run.  This module makes the
contract explicit:

* :class:`AOTFunction` wraps ``jax.jit(fn).lower(*args).compile()`` behind
  a per-abstract-signature executable cache.  Every compile is recorded in
  ``utils.profiler.COMPILE_MONITOR`` (per-function counter + signature
  log) and can be capped with ``max_recompiles``.
* :class:`CompilePool` lowers/compiles *distinct* executables concurrently
  in a thread pool (XLA compilation releases the GIL), so warm-up overlaps
  with host-side setup — env construction, replay-buffer allocation, the
  prefill rollout — instead of serializing in front of the first update.

All algorithm train loops route their update/player programs through
``fabric.compile`` (a thin veneer over :func:`compile_once` here), so the
executed program is byte-identical to the plain-``jax.jit`` one; only the
compile *cadence* becomes observable and enforceable.
"""

from __future__ import annotations

import os
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

from sheeprl_tpu.telemetry.spans import SPANS
from sheeprl_tpu.utils.profiler import COMPILE_MONITOR, RecompileLimitExceeded  # noqa: F401

_FALLBACK = object()  # cache sentinel: route this signature through plain jit


def _canon_placement(sharding: Any) -> Any:
    """Canonical placement key: every fully-on-ONE-device placement —
    committed ``SingleDeviceSharding``, an uncommitted array on the default
    device, a replicated ``NamedSharding`` over a 1-device mesh — collapses
    to the same ``("dev", platform, id)`` key.  A compiled executable
    accepts all of them interchangeably (chip_smoke.py's runs feed one
    executable a committed initial key and its own returned one), and NOT
    collapsing them burns a duplicate multi-minute compile the first time a
    program's inputs ping-pong between e.g. the host-committed initial key
    and the executable-returned one.  Multi-device shardings stay distinct
    (they genuinely select different programs).  A canonicalization miss at
    worst triggers the safe plain-jit fallback, never a wrong answer."""
    if sharding is None:
        d = jax.devices()[0]
        return ("dev", d.platform, d.id)
    try:
        dset = sharding.device_set
        if len(dset) == 1:
            d = next(iter(dset))
            return ("dev", d.platform, d.id)
    except Exception:
        pass
    return sharding


def _leaf_sig(x: Any) -> Tuple[Any, ...]:
    """Abstract signature of one argument leaf: shape / dtype / placement.

    Placement is the canonicalized sharding (see :func:`_canon_placement`;
    hashable jax sharding objects compare structurally).
    ``jax.ShapeDtypeStruct`` leaves get the same treatment so spec-based
    warm-up hits the same cache slot as the real call.
    """
    if isinstance(x, jax.ShapeDtypeStruct):
        return ("arr", x.shape, str(x.dtype), _canon_placement(x.sharding), False)
    if isinstance(x, jax.Array):
        placement = _canon_placement(x.sharding)
        return ("arr", x.shape, str(x.dtype), placement, bool(getattr(x, "weak_type", False)))
    if isinstance(x, np.ndarray):
        return ("np", x.shape, str(x.dtype))
    if isinstance(x, np.generic):
        return ("np", (), str(x.dtype))
    # dynamic python scalars: jit keys on the type, not the value
    return ("py", type(x).__name__)


def _has_tracer(leaves) -> bool:
    return any(isinstance(leaf, jax.core.Tracer) for leaf in leaves)


class AOTFunction:
    """``jax.jit`` wrapper with explicit AOT compilation and recompile audit.

    Call it like the jitted function.  The first call with a new abstract
    signature lowers + compiles ahead-of-time (recorded in
    ``COMPILE_MONITOR``); later same-signature calls dispatch straight into
    the cached executable.  ``warmup``/``compile_for`` build the executable
    without running it — from a :class:`CompilePool` thread they overlap
    compilation with host-side setup.

    Guaranteed-equivalent escape hatches: tracer arguments (the function is
    being traced inside another program) and any executable/argument
    mismatch fall through to the underlying ``jax.jit`` function, which by
    construction runs the identical program.
    """

    def __init__(
        self,
        fn: Callable,
        *,
        name: Optional[str] = None,
        static_argnums: Tuple[int, ...] = (),
        static_argnames: Tuple[str, ...] = (),
        donate_argnums: Tuple[int, ...] = (),
        in_shardings: Any = None,
        out_shardings: Any = None,
        max_recompiles: Optional[int] = None,
        monitor=None,
    ):
        self._fn = fn
        self.name = name or getattr(fn, "__name__", "<anonymous>")
        self.__name__ = self.name
        self._span_name = f"exec.{self.name}"
        self._static_argnums = tuple(static_argnums)
        self._static_argnames = tuple(static_argnames)
        # a static argument is static to jax.jit however it is passed —
        # positionally, by keyword, or omitted with a default (names resolve
        # to positions and vice versa); mirror that here so the executable
        # cache keys every spelling of the same VALUE to the same slot
        try:
            import inspect

            sig = inspect.signature(fn)
            self._param_names = tuple(sig.parameters)
            self._param_defaults = {
                p: v.default
                for p, v in sig.parameters.items()
                if v.default is not inspect.Parameter.empty
            }
        except (TypeError, ValueError):
            self._param_names = ()
            self._param_defaults = {}
        positions = {p: i for i, p in enumerate(self._param_names)}
        self._static_name_pos = frozenset(
            positions[n] for n in self._static_argnames if n in positions
        )
        self._static_names = frozenset(self._static_argnames) | frozenset(
            self._param_names[i]
            for i in self._static_argnums
            if i < len(self._param_names)
        )
        self.max_recompiles = max_recompiles
        self._monitor = monitor if monitor is not None else COMPILE_MONITOR
        jit_kwargs: Dict[str, Any] = dict(
            static_argnums=self._static_argnums or None,
            static_argnames=self._static_argnames or None,
            donate_argnums=tuple(donate_argnums),
        )
        if in_shardings is not None:
            jit_kwargs["in_shardings"] = in_shardings
        if out_shardings is not None:
            jit_kwargs["out_shardings"] = out_shardings
        self._jitted = jax.jit(fn, **jit_kwargs)
        self._lock = threading.Lock()
        self._cache: Dict[Any, Any] = {}
        self._inflight: Dict[Any, Future] = {}
        # instance-local compile audit: THIS wrapper is one compile-once
        # program, so the max_recompiles budget counts only its own
        # executables (the process-global monitor aggregates per name
        # across instances — e.g. one per run in a test process — and
        # would charge this program for compiles it never performed)
        self._compile_count = 0
        self._sig_history: list = []

    # -- plain-jit passthroughs ---------------------------------------------
    @property
    def jitted(self) -> Callable:
        """The underlying ``jax.jit`` function (implicit-compile semantics)."""
        return self._jitted

    @property
    def fn(self) -> Callable:
        """The raw (unjitted, undonated) function.  Wrappers that trace this
        program inside ANOTHER program and still use the original arguments
        afterwards (the health guard's old-vs-new select) MUST trace this,
        not the jitted callable: an inner jit's ``donate_argnums`` survives
        inlining as an aliasing hint, so XLA may clobber a donated input's
        buffer while the outer computation still reads it."""
        return self._fn

    def lower(self, *args: Any, **kwargs: Any):
        return self._jitted.lower(*args, **kwargs)

    # -- signature / static-arg handling ------------------------------------
    def _split(self, args, kwargs):
        static_idx = set(self._static_argnums) | self._static_name_pos
        dyn_args = tuple(a for i, a in enumerate(args) if i not in static_idx)
        dyn_kwargs = {
            k: v for k, v in kwargs.items() if k not in self._static_names
        }
        # canonical static key: every spelling of the same value — positional,
        # keyword, or an omitted default — resolves to the same (name, value)
        # pairs, so it selects the same executable
        static: Dict[Any, Any] = {}
        for i in sorted(static_idx):
            if i < len(args):
                key = self._param_names[i] if i < len(self._param_names) else i
                static[key] = args[i]
        for k, v in kwargs.items():
            if k in self._static_names:
                static[k] = v
        for n in self._static_names:
            if n not in static and n in self._param_defaults:
                static[n] = self._param_defaults[n]
        static_key = tuple(sorted(static.items(), key=lambda kv: str(kv[0])))
        return dyn_args, dyn_kwargs, static_key

    def _signature_and_split(self, args, kwargs):
        """(signature, dyn_args, dyn_kwargs) in ONE pass — dispatch is the
        per-env-step hot path, so the split must not run twice per call."""
        dyn_args, dyn_kwargs, static_key = self._split(args, kwargs)
        leaves, treedef = jax.tree.flatten((dyn_args, dyn_kwargs))
        if _has_tracer(leaves):
            return None, dyn_args, dyn_kwargs
        sig = (treedef, tuple(_leaf_sig(leaf) for leaf in leaves), static_key)
        return sig, dyn_args, dyn_kwargs

    def signature(self, *args: Any, **kwargs: Any):
        return self._signature_and_split(args, kwargs)[0]

    # -- compilation ---------------------------------------------------------
    def compile_for(self, *args: Any, **kwargs: Any):
        """Return the compiled executable for this signature, building it
        (and recording the compile) on first sight.  Raises
        :class:`RecompileLimitExceeded` past the budget."""
        sig = self.signature(*args, **kwargs)
        if sig is None:
            raise ValueError(f"{self.name}: cannot AOT-compile under a tracer")
        exe = self._lookup(sig, args, kwargs)
        if exe is _FALLBACK:
            raise ValueError(f"{self.name}: signature is in plain-jit fallback mode")
        return exe

    def warmup(self, *args: Any, **kwargs: Any):
        """Alias of :meth:`compile_for` — reads as intent at call sites."""
        return self.compile_for(*args, **kwargs)

    def _check_budget(self, signature) -> None:
        """Count one compile of THIS instance; raise past the budget."""
        with self._lock:
            self._compile_count += 1
            self._sig_history.append(str(signature))
            limit = self.max_recompiles
            if limit is None:
                limit = self._monitor.default_limit()
            if limit is not None and self._compile_count - 1 > int(limit):
                history = "\n  ".join(self._sig_history)
                raise RecompileLimitExceeded(
                    f"'{self.name}' compiled {self._compile_count} times, "
                    f"exceeding max_recompiles={int(limit)} (first compile is "
                    f"free). A new abstract signature reached a compile-once "
                    f"program — signature history:\n  {history}"
                )

    def _rollback_budget(self, signature) -> None:
        """Undo one ``_check_budget`` whose compile never completed.  Removes
        the MATCHING signature (searched from the end), not blindly the last
        one — two signatures of this function can compile concurrently (the
        warm-up pool overlapping the main thread) and interleave their
        begin/rollback pairs."""
        sig_str = str(signature)
        with self._lock:
            self._compile_count -= 1
            for i in range(len(self._sig_history) - 1, -1, -1):
                if self._sig_history[i] == sig_str:
                    del self._sig_history[i]
                    break

    def _lookup(self, sig, args, kwargs):
        """Executable for ``sig``: cached, inflight-awaited, or compiled now."""
        while True:
            with self._lock:
                exe = self._cache.get(sig)
                if exe is not None:
                    return exe
                fut = self._inflight.get(sig)
                if fut is None:
                    fut = Future()
                    self._inflight[sig] = fut
                    owner = True
                else:
                    owner = False
            if not owner:
                return fut.result()
            try:
                # the guard runs BEFORE the (expensive) compile: tripping the
                # budget must not first pay for the offending executable
                self._check_budget(sig[1:])
                self._monitor.begin(self.name, sig[1:])
                t0 = time.perf_counter()
                exe = self._jitted.lower(*args, **kwargs).compile()
                self._monitor.end(self.name, time.perf_counter() - t0)
            except BaseException as e:
                if not isinstance(e, RecompileLimitExceeded):
                    # the compile itself failed: roll the audit back so the
                    # executable counters (metrics, budget) reflect programs
                    # actually BUILT, and a later retry isn't double-counted
                    self._monitor.abort(self.name, sig[1:])
                    self._rollback_budget(sig[1:])
                with self._lock:
                    self._inflight.pop(sig, None)
                fut.set_exception(e)
                raise
            with self._lock:
                self._cache[sig] = exe
                self._inflight.pop(sig, None)
            fut.set_result(exe)
            return exe

    # -- dispatch -------------------------------------------------------------
    def __call__(self, *args: Any, **kwargs: Any):
        sig, dyn_args, dyn_kwargs = self._signature_and_split(args, kwargs)
        if sig is None:  # traced inside another program: inline like plain jit
            return self._jitted(*args, **kwargs)
        # the one place every fabric.compile program of every loop is
        # dispatched from: its host span (a first call's compile included).
        # No helper frame under the span: a first call lowers the program
        # below this frame, and one more frame there moved JAX's recursive
        # lowering onto a block boundary of CPython's frame stack, where
        # every call pays an mmap and a munmap (3.5 s to lower the Anakin
        # phase instead of 1.7; PERF.md section 6, PR 28)
        token = SPANS.push(self._span_name, phase=False)
        try:
            exe = self._lookup(sig, args, kwargs)
            if exe is _FALLBACK:
                return self._jitted(*args, **kwargs)
            try:
                return exe(*dyn_args, **dyn_kwargs)
            except (TypeError, ValueError):
                # argument/executable mismatch our coarse signature missed
                # (argument-validation errors fire before execution, so
                # donated buffers are still intact) — plain jit is always
                # correct; pin this signature to the fallback so the cost is
                # paid once.  The implicit-jit call re-traces for the TRUE
                # signature: count that compile (and hold it to the budget)
                # so retraces stay visible exactly where the coarse scheme
                # failed — but only once the call SUCCEEDS: genuinely bad
                # arguments raise the same error from plain jit without
                # compiling anything, and must not leave a phantom
                # executable in the audit.  Only LATER drift inside this
                # pinned bucket escapes the audit.
                fb_sig = ("jit-fallback",) + sig[1:]
                self._check_budget(fb_sig)
                try:
                    out = self._jitted(*args, **kwargs)
                except BaseException:
                    self._rollback_budget(fb_sig)
                    raise
                self._monitor.begin(self.name, fb_sig)
                with self._lock:
                    self._cache[sig] = _FALLBACK
                return out
        finally:
            SPANS.pop(token)

    def cache_size(self) -> int:
        with self._lock:
            return len(self._cache)


def state_io_shardings(
    param_shardings: Any,
    opt_shardings: Any,
    n_extra_in: int,
    n_extra_out: int = 1,
) -> Tuple[Tuple[Any, ...], Tuple[Any, ...]]:
    """``(in_shardings, out_shardings)`` for the canonical train-phase
    calling convention ``f(params, opt_state, *data) -> (params, opt_state,
    *aux)`` shared by every algo's scanned update program.

    ``param_shardings``/``opt_shardings`` are ``NamedSharding`` pytrees —
    normally ``sharding.shardings_of(fabric.shard_params(...))``, i.e. the
    partition-rules placement.  Pinning them on BOTH sides of the program
    (and donating argnums 0/1 at the call site) is what makes a sharded
    train step update params and optimizer state IN PLACE: the optimizer
    moments keep exactly their params' column/row sharding across every
    update, and XLA reuses the donated buffers instead of materializing a
    gathered copy.  The ``None`` entries for data/key/counter arguments and
    aux outputs mean 'unspecified' — jit infers those from the arguments
    (the batch keeps its ``data``-axis sharding) and the computation.
    """
    return (
        (param_shardings, opt_shardings) + (None,) * int(n_extra_in),
        (param_shardings, opt_shardings) + (None,) * int(n_extra_out),
    )


def compile_once(
    fn: Callable,
    *,
    name: Optional[str] = None,
    static_argnums: Tuple[int, ...] = (),
    static_argnames: Tuple[str, ...] = (),
    donate_argnums: Tuple[int, ...] = (),
    in_shardings: Any = None,
    out_shardings: Any = None,
    max_recompiles: Optional[int] = None,
) -> AOTFunction:
    """Module-level constructor for factories that have no fabric in scope
    (``make_sac_train_fns``, the decoupled PPO train-fn builder...);
    ``Fabric.compile`` delegates here."""
    return AOTFunction(
        fn,
        name=name,
        static_argnums=static_argnums,
        static_argnames=static_argnames,
        donate_argnums=donate_argnums,
        in_shardings=in_shardings,
        out_shardings=out_shardings,
        max_recompiles=max_recompiles,
    )


class CompilePool:
    """Parallel compile warm-up over a shared thread pool.

    XLA compilation is C++ work that releases the GIL, so the *distinct*
    executables of a run (update step, player step, eval step, per-preset
    variants) lower and compile concurrently while the host builds envs and
    buffers.  Submissions are best-effort by design: a warm-up failure is
    swallowed at ``join`` (the executable would simply compile inline at
    first call), EXCEPT the recompile guard, which must stay a hard error.
    """

    def __init__(self, max_workers: Optional[int] = None):
        if max_workers is None:
            max_workers = max(2, min(4, (os.cpu_count() or 2)))
        self._executor = ThreadPoolExecutor(
            max_workers=max_workers, thread_name_prefix="sheeprl-compile"
        )
        self._futures: list[Future] = []
        self._hard_errors: list[BaseException] = []
        self._lock = threading.Lock()

    def _track(self, fut: Future) -> Future:
        """Self-draining bookkeeping: completed futures remove themselves, so
        a long-lived process whose loops submit warm-ups but never ``join``
        (the fire-and-forget player warm-up) doesn't grow ``_futures`` — and
        their captured args — without bound.  Recompile-budget trips are
        stashed so a later ``join`` still surfaces them; they are never truly
        lost even without a join, because the real call re-enters the same
        budget check and raises at the call site."""
        with self._lock:
            self._futures.append(fut)

        def _drain(f: Future) -> None:
            exc = f.exception()
            with self._lock:
                try:
                    self._futures.remove(f)
                except ValueError:
                    # a join() snapshot owns this future and will observe
                    # its exception itself — stashing here too would make a
                    # LATER join spuriously re-raise an already-surfaced trip
                    return
                if isinstance(exc, RecompileLimitExceeded):
                    self._hard_errors.append(exc)

        fut.add_done_callback(_drain)
        return fut

    def submit(self, aot_fn: AOTFunction, *args: Any, **kwargs: Any) -> Future:
        return self._track(self._executor.submit(aot_fn.compile_for, *args, **kwargs))

    def submit_fn(self, fn: Callable, *args: Any, **kwargs: Any) -> Future:
        """Run an arbitrary warm-up thunk (e.g. a stage builder) in the pool."""
        return self._track(self._executor.submit(fn, *args, **kwargs))

    def join(self, timeout: Optional[float] = None) -> None:
        """Wait for all outstanding warm-ups.  Re-raises only
        :class:`RecompileLimitExceeded`; other warm-up failures degrade to
        inline compilation at first call."""
        with self._lock:
            futures, self._futures = self._futures, []
        for fut in futures:
            try:
                fut.result(timeout=timeout)
            except RecompileLimitExceeded:
                raise  # snapshot futures are reported here, never stashed
            except Exception:
                pass
        with self._lock:
            errs, self._hard_errors = list(self._hard_errors), []
        if errs:
            # a fire-and-forget warm-up (self-drained before this join)
            # tripped the budget: surface it now
            raise errs[0]

    def shutdown(self) -> None:
        self._executor.shutdown(wait=False, cancel_futures=True)


def warmup_batch_ladder(
    aot_fn: AOTFunction,
    spec_fn: Callable[[int], Tuple[Any, ...]],
    batch_sizes: Tuple[int, ...],
    pool: Optional["CompilePool"] = None,
    join: bool = True,
    timeout: Optional[float] = None,
) -> list:
    """AOT-compile ``aot_fn`` at every batch size of a serving ladder.

    ``spec_fn(batch)`` returns the positional argument tuple for one ladder
    rung — concrete arrays and/or ``jax.ShapeDtypeStruct`` leaves, exactly
    as the steady-state dispatch will pass them (the abstract signature
    keys the executable cache, so warm-up specs must match dispatch leaves
    kind-for-kind).  Distinct rungs compile concurrently on the shared
    :class:`CompilePool`; with ``join=True`` this blocks until the whole
    ladder is warm, so a server can guarantee ZERO steady-state compiles
    before admitting traffic.
    """
    pool = pool if pool is not None else get_compile_pool()
    futures = [pool.submit(aot_fn, *spec_fn(int(b))) for b in batch_sizes]
    if join:
        pool.join(timeout)
    return futures


_POOL: Optional[CompilePool] = None
_POOL_LOCK = threading.Lock()


def get_compile_pool() -> CompilePool:
    """The process-wide warm-up pool (lazily created)."""
    global _POOL
    with _POOL_LOCK:
        if _POOL is None:
            _POOL = CompilePool()
        return _POOL
