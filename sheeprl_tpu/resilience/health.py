"""In-loop training-health sentinels: NaN-skip + divergence rollback.

A production run that hits a non-finite loss or gradient does not crash —
it silently destroys its own parameters and keeps training on garbage.
The supervisor (``sheeprl_tpu/supervisor/``) can restart a *dead* run;
only the loop itself can refuse a *poisoned* update.  This module guards
the update dispatch with two sentinels (docs/supervisor.md):

* a **non-finite guard**, compiled INTO the train trace: after the algo's
  own update math, the guarded program checks the window's loss (and, by
  default, the freshly-updated params) for NaN/Inf and SELECTS the old
  params/opt-state when the check fails — the poisoned window is skipped,
  bit-identically, with zero extra host↔device traffic per step.  The
  decision, counters and loss statistics live in a tiny device-resident
  :class:`HealthState` threaded through the executable like the grad-step
  counter, so ``cache_size() == 1`` and the transfer guard are preserved.
* a **loss-spike / divergence detector**: an EMA of the (finite) window
  loss with a consecutive-spike counter.  When ``patience`` consecutive
  windows spike past ``spike_factor``, the run is declared diverged; the
  host-side :meth:`HealthSentinel.poll` (called once per poll interval,
  NOT per step) then triggers a rollback to the last committed checkpoint
  (``health.divergence.action=rollback``) instead of continuing on
  garbage params, or just reports (``action=none``, the default).

Chaos drills exercise both paths deterministically through the
``update.grads`` fault site (``resilience/faults.py``): ``nonfinite`` and
``divergence`` specs are resolved at trace-BUILD time into the guarded
executable (``at=``/``every=`` count guarded dispatches), so a planted
fault needs no host hook in the hot path and survives the transfer guard.

Granularity: the loops dispatch updates in windows (``update_chunks``);
the guard skips the whole poisoned *window* — the dispatch is one fused
executable and the device cannot report which inner step went bad without
breaking the single-program contract.  Windows are short (the chunk law),
and a skipped window costs exactly one window of progress.

Everything is reported as ``Health/*`` through the telemetry hub and as
``health.*`` flight-recorder events, so a postmortem shows what the
sentinels saw.
"""

from __future__ import annotations

import warnings
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from sheeprl_tpu.resilience.faults import active_plan


class DivergenceError(RuntimeError):
    """Training diverged and in-loop rollback is unavailable or exhausted.

    Raised by the sentinel when the divergence detector fires but there is
    no committed checkpoint to roll back to, the rollback budget
    (``health.divergence.max_rollbacks``) is spent, or the loop does not
    implement in-loop rollback.  The exception reaches ``cli.run``'s crash
    path (postmortem + final flush); the supervisor classifies it and
    restarts with ``checkpoint.resume_from=auto`` — rollback through the
    process boundary."""


class HealthState(NamedTuple):
    """Device-resident sentinel state, threaded through the guarded
    executable as data (one tiny replicated pytree — never rebuilt
    host-side per window, so the steady state performs no extra H2D)."""

    dispatches: Any  # int32: guarded update dispatches (windows) so far
    applied: Any  # int32: windows whose update was applied
    skipped: Any  # int32: windows skipped by the non-finite guard
    nonfinite_loss: Any  # int32: windows whose loss itself was non-finite
    last_loss: Any  # float32: newest FINITE window loss
    ema: Any  # float32: EMA of the finite window loss
    spike_run: Any  # int32: consecutive spiking windows
    spike_total: Any  # int32: total spiking windows
    diverged: Any  # int32: sticky divergence flag


def _zero_state(dispatches: int = 0) -> HealthState:
    # jnp (XLA-owned) scalars, NOT numpy: the state is DONATED into the
    # guarded executable on its first dispatch, and a CPU `device_put` of a
    # numpy scalar may zero-copy-borrow the numpy buffer — donating a
    # borrowed buffer hands XLA memory it does not own (heap corruption
    # that surfaces as a later unrelated segfault)
    import jax.numpy as jnp

    return HealthState(
        dispatches=jnp.full((), int(dispatches), jnp.int32),
        applied=jnp.zeros((), jnp.int32),
        skipped=jnp.zeros((), jnp.int32),
        nonfinite_loss=jnp.zeros((), jnp.int32),
        last_loss=jnp.zeros((), jnp.float32),
        ema=jnp.zeros((), jnp.float32),
        spike_run=jnp.zeros((), jnp.int32),
        spike_total=jnp.zeros((), jnp.int32),
        diverged=jnp.zeros((), jnp.int32),
    )


def _is_float_leaf(x: Any) -> bool:
    import jax.numpy as jnp

    dtype = getattr(x, "dtype", None)
    return dtype is not None and jnp.issubdtype(dtype, jnp.floating)


def tree_finite(tree: Any) -> Any:
    """In-trace AND-reduce of ``isfinite`` over every floating leaf."""
    import jax
    import jax.numpy as jnp

    ok = jnp.bool_(True)
    for leaf in jax.tree.leaves(tree):
        if _is_float_leaf(leaf):
            ok = ok & jnp.all(jnp.isfinite(leaf))
    return ok


def tree_select(pred: Any, new: Any, old: Any) -> Any:
    """Elementwise select: ``new`` where ``pred`` else ``old`` (exact —
    ``where(True, a, b)`` is ``a`` bit-for-bit, so an applied window is
    byte-identical to the unguarded update)."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda n, o: jnp.where(pred, n, o), new, old)


def loss_scalar(metrics: Any) -> Any:
    """One f32 scalar summarizing a train dispatch's loss pytree: the sum
    of the means of every floating leaf.  Algorithms return different loss
    shapes (SAC a 3-tuple, Dreamer a 10-tuple) — the sentinel only needs a
    consistent scalar whose finiteness and trend track the update's."""
    import jax
    import jax.numpy as jnp

    leaves = [l for l in jax.tree.leaves(metrics) if _is_float_leaf(l)]
    if not leaves:
        return jnp.float32(0.0)
    total = jnp.float32(0.0)
    for leaf in leaves:
        total = total + jnp.mean(leaf).astype(jnp.float32)
    return total


def _spec_fire_count(spec: Any, lo: int, hi: int) -> int:
    """How many guarded dispatches in ``(lo, hi]`` the spec fires at —
    pure host arithmetic, mirroring :meth:`HealthSentinel._fire_pred`."""
    fires = 0
    if spec.at is not None and lo < int(spec.at) <= hi:
        fires += 1
    if spec.every is not None and int(spec.every) > 0:
        e = int(spec.every)
        top = hi // e
        if spec.max_fires is not None:
            top = min(top, int(spec.max_fires))
        fires += max(0, top - lo // e)
    return fires


class HealthSentinel:
    """Host-side controller for the in-trace sentinels of ONE train loop.

    Lifecycle (see ``algos/sac/sac.py`` for the reference wiring):

    1. ``HealthSentinel.from_config(cfg, fabric)`` — ``None`` when
       ``health.enabled=false`` (the guard is compiled OUT; call sites keep
       the exact unguarded program).
    2. ``train_phase = fabric.compile(sentinel.wrap(train_phase), ...)`` —
       the guarded program: ``(h, p, o, *rest) -> (h, p, o, metrics)``.
    3. ``h = sentinel.init_state()`` — the replicated device state.
    4. per poll interval: ``action = sentinel.poll(h, policy_step)`` —
       fetches the tiny state (the only D2H, outside the guarded window),
       publishes ``Health/*`` through the hub, records recorder events,
       and returns ``"rollback"`` when the divergence detector fired.
    """

    HUB_SOURCE = "health"

    def __init__(self, hcfg: Any, fabric: Any = None):
        hcfg = hcfg or {}
        self.fabric = fabric
        self.check_params = bool(hcfg.get("check_params", True))
        self.poll_every = max(1, int(hcfg.get("poll_every_updates", 25) or 1))
        self.ema_decay = float(hcfg.get("ema_decay", 0.99))
        self.spike_factor = float(hcfg.get("spike_factor", 10.0))
        self.spike_min = float(hcfg.get("spike_min", 1.0))
        self.min_windows = int(hcfg.get("min_windows", 20))
        self.patience = max(1, int(hcfg.get("patience", 3) or 1))
        dcfg = hcfg.get("divergence") or {}
        self.action = str(dcfg.get("action", "none"))
        if self.action not in ("none", "rollback"):
            raise ValueError(f"health.divergence.action must be none|rollback, got {self.action!r}")
        self.max_rollbacks = int(dcfg.get("max_rollbacks", 3))
        self.divergence_scale = float(dcfg.get("fault_scale", 1e6))
        self.rollbacks = 0
        # planted update.grads faults, resolved ONCE (the plan is installed
        # before the loops build their programs — cli.run guarantees it)
        plan = active_plan()
        self._trace_specs: List[Any] = (
            plan.specs_for("update.grads") if plan is not None else []
        )
        self._metrics: Dict[str, float] = {}
        self._registered = False
        self._reset_baseline()

    # -- construction --------------------------------------------------------
    @classmethod
    def from_config(cls, cfg: Any, fabric: Any = None) -> Optional["HealthSentinel"]:
        hcfg = (cfg.get("health") or {}) if hasattr(cfg, "get") else {}
        if not hcfg.get("enabled", True):
            return None
        return cls(hcfg, fabric)

    def _reset_baseline(self) -> None:
        self._prev = {"dispatches": 0, "skipped": 0, "nonfinite_loss": 0, "spike_total": 0}
        self._diverged_reported = False

    def init_state(self, dispatches: int = 0) -> HealthState:
        """A fresh replicated device :class:`HealthState` (also resets the
        host-side poll baselines).  ``dispatches`` seeds the guarded-
        dispatch counter — see :meth:`reseed_state`."""
        self._reset_baseline()
        self._prev["dispatches"] = int(dispatches)
        zero = _zero_state(dispatches)
        if self.fabric is not None:
            return self.fabric.replicate(zero)
        return zero

    def reseed_state(self) -> HealthState:
        """Fresh state after a rollback: counters and the sticky diverged
        flag cleared, but the guarded-dispatch counter PRESERVED — planted
        ``update.grads`` schedules and the ``min_windows`` warmup key on
        it, and a rollback must not replay them."""
        return self.init_state(dispatches=self._prev["dispatches"])

    # -- the in-trace guard --------------------------------------------------
    def _fire_pred(self, d: Any, kind: str) -> Optional[Any]:
        """OR of the planted ``update.grads`` schedules of ``kind`` at
        guarded-dispatch number ``d`` (in-trace; None = nothing planted, so
        nothing is compiled in)."""
        import jax.numpy as jnp

        preds = []
        for spec in self._trace_specs:
            if spec.kind != kind:
                continue
            if spec.at is not None:
                preds.append(d == jnp.int32(int(spec.at)))
            if spec.every is not None and int(spec.every) > 0:
                e = jnp.int32(int(spec.every))
                cond = (d % e) == 0
                if spec.max_fires is not None:
                    cond = cond & ((d // e) <= jnp.int32(int(spec.max_fires)))
                preds.append(cond)
        if not preds:
            return None
        fire = preds[0]
        for p in preds[1:]:
            fire = fire | p
        return fire

    def wrap(self, phase: Any) -> Any:
        """Wrap a train phase obeying the canonical convention
        ``phase(p, o_state, *data) -> (p, o_state, metrics)`` into the
        guarded program ``guarded(h, p, o_state, *data) -> (h, p, o_state,
        metrics)``.  Call sites compile the result with
        ``donate_argnums=(0, 1, 2)``.  ``phase`` may be raw or an already
        compile-once'd :class:`~sheeprl_tpu.parallel.compile.AOTFunction` —
        the guard traces the RAW function (``AOTFunction.fn``), never the
        jitted one: the inner jit's ``donate_argnums=(0, 1)`` would survive
        inlining as an aliasing hint, and the guard re-reads ``p``/``o``
        AFTER the inner call (the old-vs-new select), so an honored inner
        donation can clobber the very buffers the select reads."""
        import jax
        import jax.numpy as jnp

        from sheeprl_tpu.parallel.compile import AOTFunction

        if isinstance(phase, AOTFunction):
            phase = phase.fn

        check_params = self.check_params
        decay = jnp.float32(self.ema_decay)
        factor = jnp.float32(self.spike_factor)
        smin = jnp.float32(self.spike_min)
        min_windows = jnp.int32(self.min_windows)
        patience = jnp.int32(self.patience)
        div_scale = jnp.float32(self.divergence_scale)

        def guarded(h: HealthState, p: Any, o_state: Any, *rest: Any, **kw: Any):
            new_p, new_o, metrics = phase(p, o_state, *rest, **kw)
            d = h.dispatches + jnp.int32(1)

            # planted chaos, compiled from the fault plan (drills only —
            # with no update.grads specs these branches emit NO ops)
            nan_fire = self._fire_pred(d, "nonfinite")
            div_fire = self._fire_pred(d, "divergence")
            loss = loss_scalar(metrics)
            if nan_fire is not None:
                poison = jnp.where(nan_fire, jnp.float32(jnp.nan), jnp.float32(0.0))
                new_p = jax.tree.map(
                    lambda x: x + poison.astype(x.dtype) if _is_float_leaf(x) else x,
                    new_p,
                )
                loss = loss + poison
            if div_fire is not None:
                loss = loss * jnp.where(div_fire, div_scale, jnp.float32(1.0))

            # -- non-finite guard: skip the poisoned window ------------------
            loss_ok = jnp.isfinite(loss)
            ok = loss_ok & tree_finite(new_p) if check_params else loss_ok
            p_out = tree_select(ok, new_p, p)
            o_out = tree_select(ok, new_o, o_state)

            # -- spike / divergence detector over the FINITE loss stream -----
            loss_f = jnp.where(loss_ok, loss, h.last_loss)
            seeded = (h.applied + h.skipped) > 0
            ema_prev = jnp.where(seeded, h.ema, loss_f)
            warm = d >= min_windows
            is_spike = loss_ok & warm & (
                (loss_f - ema_prev) > factor * (jnp.abs(ema_prev) + smin)
            )
            # a spiking window is NOT absorbed into the EMA: repeated spikes
            # must stay spikes, not drag the baseline up under them
            ema_new = jnp.where(is_spike, ema_prev, decay * ema_prev + (1.0 - decay) * loss_f)
            spike_run = jnp.where(is_spike, h.spike_run + 1, jnp.int32(0))
            diverged = jnp.maximum(h.diverged, (spike_run >= patience).astype(jnp.int32))

            oki = ok.astype(jnp.int32)
            h2 = HealthState(
                dispatches=d,
                applied=h.applied + oki,
                skipped=h.skipped + (jnp.int32(1) - oki),
                nonfinite_loss=h.nonfinite_loss + (jnp.int32(1) - loss_ok.astype(jnp.int32)),
                last_loss=loss_f,
                ema=ema_new,
                spike_run=spike_run,
                spike_total=h.spike_total + is_spike.astype(jnp.int32),
                diverged=diverged,
            )
            return h2, p_out, o_out, metrics

        guarded.__name__ = f"health_guarded[{getattr(phase, '__name__', 'train_phase')}]"
        return guarded

    # -- hub / recorder plumbing ---------------------------------------------
    def register(self) -> "HealthSentinel":
        from sheeprl_tpu.telemetry.hub import HUB

        HUB.register(self.HUB_SOURCE, self.metrics)
        self._registered = True
        return self

    def close(self) -> None:
        if self._registered:
            from sheeprl_tpu.telemetry.hub import HUB

            HUB.unregister(self.HUB_SOURCE)
            self._registered = False

    def metrics(self) -> Dict[str, float]:
        """The newest polled ``Health/*`` snapshot (a hub source; empty
        until the first poll, so an idle sentinel emits nothing)."""
        return dict(self._metrics)

    # -- per-interval host poll ----------------------------------------------
    def should_poll(self, update: int, total_iters: int) -> bool:
        return update % self.poll_every == 0 or update >= total_iters

    def poll(self, h: HealthState, policy_step: int) -> str:
        """Fetch the device state (tiny, once per poll interval), publish
        metrics/events, and return the pending action: ``"none"`` or
        ``"rollback"``.  The fetch waits for the dispatch that produced
        ``h``: the loop's ``health.poll`` span."""
        from sheeprl_tpu.telemetry.spans import span

        with span("health.poll", phase=False):
            return self._poll(h, policy_step)

    def _poll(self, h: HealthState, policy_step: int) -> str:
        import jax

        vals = jax.device_get(h)
        d = int(vals.dispatches)
        skipped = int(vals.skipped)
        nonfinite = int(vals.nonfinite_loss)
        spike_total = int(vals.spike_total)
        diverged = bool(int(vals.diverged))

        # planted-fault accounting: the schedule is deterministic, so the
        # host can mirror exactly which guarded dispatches in the polled
        # range fired — landing fault.injected recorder events + the
        # Resilience/* injection counters without any in-trace callback
        lo = self._prev["dispatches"]
        if d > lo and self._trace_specs:
            from sheeprl_tpu.utils.profiler import RESILIENCE_MONITOR

            for spec in self._trace_specs:
                for _ in range(_spec_fire_count(spec, lo, d)):
                    RESILIENCE_MONITOR.record_injection("update.grads", spec.kind)

        from sheeprl_tpu.telemetry.recorder import RECORDER

        new_skips = skipped - self._prev["skipped"]
        if new_skips > 0:
            RECORDER.record(
                "health.skip",
                count=new_skips,
                nonfinite_loss=nonfinite - self._prev["nonfinite_loss"],
                step=int(policy_step),
            )
        new_spikes = spike_total - self._prev["spike_total"]
        if new_spikes > 0:
            RECORDER.record(
                "health.spike",
                count=new_spikes,
                loss=float(vals.last_loss),
                ema=float(vals.ema),
                step=int(policy_step),
            )
        if diverged and not self._diverged_reported:
            self._diverged_reported = True
            RECORDER.record("health.diverged", step=int(policy_step), ema=float(vals.ema))
            if self.action != "rollback":
                warnings.warn(
                    f"training-health sentinel: loss diverged at step {policy_step} "
                    "(health.divergence.action=none — continuing; set "
                    "health.divergence.action=rollback to auto-restore the last "
                    "committed checkpoint)",
                    RuntimeWarning,
                )

        self._prev = {
            "dispatches": d,
            "skipped": skipped,
            "nonfinite_loss": nonfinite,
            "spike_total": spike_total,
        }
        self._metrics = {
            "Health/windows": float(d),
            "Health/applied": float(vals.applied),
            "Health/skipped": float(skipped),
            "Health/nonfinite_loss": float(nonfinite),
            "Health/loss_last": float(vals.last_loss),
            "Health/loss_ema": float(vals.ema),
            "Health/spike_windows": float(spike_total),
            "Health/diverged": float(int(diverged)),
            "Health/rollbacks": float(self.rollbacks),
        }
        if diverged and self.action == "rollback":
            return "rollback"
        return "none"

    # -- rollback budget ------------------------------------------------------
    def begin_rollback(self, policy_step: int) -> None:
        """Count one rollback attempt; raise :class:`DivergenceError` past
        the budget (a run that keeps diverging after ``max_rollbacks``
        restores is deterministically sick — surface it, don't loop)."""
        self.rollbacks += 1
        if self.rollbacks > self.max_rollbacks:
            raise DivergenceError(
                f"training diverged at step {policy_step} and the in-loop "
                f"rollback budget (health.divergence.max_rollbacks="
                f"{self.max_rollbacks}) is exhausted"
            )

    def rolled_back(self, policy_step: int, resume_step: Any) -> None:
        from sheeprl_tpu.telemetry.recorder import RECORDER

        RECORDER.record(
            "health.rollback", step=int(policy_step), resume_step=str(resume_step)
        )
        self._metrics["Health/rollbacks"] = float(self.rollbacks)
