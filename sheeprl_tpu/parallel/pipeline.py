"""MPMD-style pipeline parallelism for ≥5B world models (ROADMAP item 3).

The PR 7 rules engine shards the big matmuls over a ``model`` mesh axis, but
the RSSM's sequential scan leaves that axis idle between layers (DV3-XL
read 8.8% MFU data-parallel-only in the 2026-07-31 capture, deleted in PR 23, see git history).  "Scaling Deep Learning Training with MPMD Pipeline Parallelism"
(arXiv:2412.14374) recovers exactly this idle time by splitting the model
into stages and streaming microbatches through them; the Podracer line
(arXiv:2104.06272) is the same keep-the-chips-busy discipline this repo
already applies to rollouts.  This module applies it to the update step.

Three cooperating pieces:

**Stage partitioning** — the dreamer world model splits into a linear chain
of stages (encoder → RSSM → heads/decoder).  On the mesh, a new ``pipeline``
axis composes with the existing ``data``/``model`` axes
(``fabric.mesh_shape={data: D, pipeline: S, model: K}``):
:func:`compose_pipeline_rules` rewrites the curated partition-rule table so
every ``model``-sharded weight dimension tiles over the ``(pipeline, model)``
product — the single-controller GSPMD realization of "stages mapped to mesh
sub-groups" (each sub-group owns a ``1/(S·K)`` weight slice, which is what
unlocks ≥5B world models no 2-D mesh can hold).  With a ``pipeline`` axis
and no ``model`` axis, weights tile over ``pipeline`` alone.

**1F1B microbatch schedule** — :func:`pipeline_value_and_grad` runs the
stage chain over ``pipeline.microbatches`` slices of the sequence batch in
one-forward-one-backward order (:func:`one_f_one_b`), inside the SAME traced
program as the rest of the train phase (a ``lax``-level schedule: the tick
order is unrolled at trace time, so the compile-once law is untouched —
``cache_size()==1`` across windows under the armed transfer guard).  Each
microbatch's backward runs as early as its cotangents exist, so at most
``S - s`` forward activations per stage are ever live (the 1F1B memory
bound), and the per-unit gradient accumulation chain pins XLA's liveness to
the schedule order.  Inter-stage activation buffers stay on device and are
donated in place by XLA's buffer reuse; the HOST-level analogue
(:func:`compile_stage_pair`, the per-stage measurement harness) donates them
explicitly — donating a stage output and reading it again for the backward
is the ``use-after-donate`` hazard graftlint's curated table now covers.

**Sample invariance law** — stage functions must be DETERMINISTIC and
microbatch-invariant: a PRNG draw at microbatch shape would give different
samples than the full-batch baseline (bit-streams depend on shape), turning
a scheduling choice into a numerics change.  Callers hoist all sampling
noise out of the stages (draw at full batch shape with the baseline's exact
keys, slice per microbatch — ``OneHotCategorical.rsample_from_noise``),
which is what makes DP-vs-pipelined parity hold at reassociation level
(tests/test_parallel/test_pipeline.py; tolerance tiers in
tests/test_parallel/test_tensor_parallel.py).

Telemetry: the schedule's bubble fraction ``(S-1)/(M+S-1)`` is a
first-class metric (``Pipeline/bubble_frac`` through the hub;
``Phase/pipeline.stage.*`` spans from :func:`compile_stage_pair`'s
programs — taxonomy in docs/telemetry.md).  Tuning guide and schedule diagram: docs/pipeline.md.
"""

from __future__ import annotations

import operator
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = [
    "PipelineSpec",
    "resolve_pipeline",
    "one_f_one_b",
    "bubble_fraction",
    "split_microbatches",
    "merge_microbatches",
    "pipeline_value_and_grad",
    "chunked_rows",
    "compose_pipeline_rules",
    "compile_stage_pair",
    "register_pipeline_metrics",
    "PIPELINE_ALGOS",
]

#: algorithms whose train-phase builders implement the stage split.  The
#: dreamer-family loop validates against this so an enabled pipeline on an
#: unsupported algo fails at build time, not silently.
PIPELINE_ALGOS: Tuple[str, ...] = ("dreamer_v3",)

#: the canonical pipeline mesh-axis name (composes with "data"/"model")
PIPELINE_AXIS = "pipeline"


# --------------------------------------------------------------------------
# config resolution
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class PipelineSpec:
    """Resolved ``pipeline`` config group (configs/pipeline/default.yaml)."""

    stages: int = 1
    microbatches: int = 1
    axis: str = PIPELINE_AXIS
    schedule: str = "1f1b"
    #: row-chunking factor for the imagination batch's wide head
    #: evaluations (:func:`chunked_rows`); 1 = full-batch
    imagination_microbatches: int = 1

    @property
    def enabled(self) -> bool:
        return self.stages > 1 or self.microbatches > 1

    @property
    def bubble_frac(self) -> float:
        return bubble_fraction(self.stages, self.microbatches)

    def check_algo(self, algo_name: str) -> None:
        if self.enabled and algo_name not in PIPELINE_ALGOS:
            raise ValueError(
                f"pipeline parallelism (pipeline.stages={self.stages}, "
                f"pipeline.microbatches={self.microbatches}) is implemented for "
                f"{PIPELINE_ALGOS}, not '{algo_name}'; set pipeline.stages=1 "
                "and pipeline.microbatches=1 (configs/pipeline/default.yaml)"
            )

    def metrics(self) -> Dict[str, float]:
        """``Pipeline/*`` metrics for the telemetry hub."""
        if not self.enabled:
            return {}
        return {
            "Pipeline/stages": float(self.stages),
            "Pipeline/microbatches": float(self.microbatches),
            "Pipeline/bubble_frac": self.bubble_frac,
        }


def resolve_pipeline(cfg: Any) -> PipelineSpec:
    """``cfg.pipeline`` → validated :class:`PipelineSpec`.

    Accepts the full composed config or the group dict itself; a missing
    group resolves to the disabled spec (bare ``Fabric`` users, old exps)."""
    group = cfg.get("pipeline") if hasattr(cfg, "get") else None
    if group is None:
        group = {}
    stages = int(group.get("stages", 1))
    microbatches = int(group.get("microbatches", 1))
    schedule = str(group.get("schedule", "1f1b"))
    imag = int(group.get("imagination_microbatches", 1))
    if stages < 1 or microbatches < 1 or imag < 1:
        raise ValueError(
            f"pipeline.stages ({stages}), pipeline.microbatches ({microbatches}) "
            f"and pipeline.imagination_microbatches ({imag}) must all be >= 1"
        )
    if schedule != "1f1b":
        raise ValueError(
            f"pipeline.schedule='{schedule}' is not supported; the only "
            "implemented schedule is '1f1b' (docs/pipeline.md)"
        )
    if stages > 1 and microbatches < stages:
        raise ValueError(
            f"pipeline.microbatches ({microbatches}) must be >= pipeline.stages "
            f"({stages}): with fewer microbatches than stages the 1F1B schedule "
            f"is all bubble (bubble_frac="
            f"{bubble_fraction(stages, max(microbatches, 1)):.2f}); raise "
            "microbatches or lower stages"
        )
    return PipelineSpec(
        stages=stages, microbatches=microbatches,
        schedule=schedule, imagination_microbatches=imag,
    )


# --------------------------------------------------------------------------
# the 1F1B schedule
# --------------------------------------------------------------------------

def bubble_fraction(stages: int, microbatches: int) -> float:
    """Idle fraction of the 1F1B schedule: ``(S-1)/(M+S-1)``.

    ``M + S - 1`` ticks drain ``M`` microbatches through ``S`` stages; the
    ``S - 1`` ramp-up/ramp-down ticks are bubble.  Per-stage-balanced
    approximation: measured per-stage times need a run on the chip (no
    cell yet: ROADMAP D5, R8)."""
    s, m = int(stages), int(microbatches)
    if s <= 1:
        return 0.0
    return (s - 1) / (m + s - 1)


def one_f_one_b(stages: int, microbatches: int) -> List[Tuple[str, int, int]]:
    """The one-forward-one-backward unit order: ``[(op, stage, microbatch)]``
    with ``op`` in ``{"F", "B"}``.

    Tick simulation of the classic non-interleaved 1F1B schedule: each stage
    runs at most one unit per tick; stage ``s`` ramps up until ``S - s``
    forwards are in flight, then alternates backward/forward (backwards
    drain towards stage 0).  Dependencies are enforced against the PREVIOUS
    tick's completions — the returned flat list (ticks concatenated in
    order) is therefore a valid execution order for
    :func:`pipeline_value_and_grad`'s trace-time unrolling, and its liveness
    profile (≤ ``S - s`` live activations at stage ``s``) is the 1F1B
    memory bound."""
    S, M = int(stages), int(microbatches)
    if S < 1 or M < 1:
        raise ValueError(f"one_f_one_b: need stages >= 1 and microbatches >= 1, got ({S}, {M})")
    order: List[Tuple[str, int, int]] = []
    f_cnt = [0] * S  # forwards completed per stage (microbatches 0..f_cnt-1)
    b_cnt = [0] * S  # backwards completed per stage
    max_ticks = 4 * S * (M + S)  # generous; the schedule needs M + S - 1
    for _ in range(max_ticks):
        if all(f == M for f in f_cnt) and all(b == M for b in b_cnt):
            return order
        f_snap, b_snap = list(f_cnt), list(b_cnt)
        progressed = False
        for s in range(S):
            in_flight = f_cnt[s] - b_cnt[s]
            cap = S - s  # 1F1B in-flight bound at stage s
            can_f = f_cnt[s] < M and (s == 0 or f_cnt[s] < f_snap[s - 1])
            can_b = (
                b_cnt[s] < M
                and b_cnt[s] < f_snap[s]
                and (s == S - 1 or b_cnt[s] < b_snap[s + 1])
            )
            if can_b and (in_flight >= cap or f_cnt[s] == M):
                order.append(("B", s, b_cnt[s]))
                b_cnt[s] += 1
                progressed = True
            elif can_f and in_flight < cap:
                order.append(("F", s, f_cnt[s]))
                f_cnt[s] += 1
                progressed = True
            elif can_b:
                order.append(("B", s, b_cnt[s]))
                b_cnt[s] += 1
                progressed = True
        if not progressed:
            raise RuntimeError(
                f"one_f_one_b: schedule wedged at f={f_cnt} b={b_cnt} "
                f"(stages={S}, microbatches={M}) — internal scheduling bug"
            )
    raise RuntimeError(
        f"one_f_one_b: schedule did not drain within {max_ticks} ticks "
        f"(stages={S}, microbatches={M}) — internal scheduling bug"
    )


# --------------------------------------------------------------------------
# microbatch plumbing
# --------------------------------------------------------------------------

def split_microbatches(tree: Any, microbatches: int, axis: int = 1) -> Any:
    """Split every leaf's ``axis`` into a LEADING microbatch axis:
    ``(..., M*b, ...) → (M, ..., b, ...)`` with contiguous row chunks
    (microbatch ``m`` holds rows ``[m*b, (m+1)*b)`` — the exact inverse of
    :func:`merge_microbatches`, so reassembled outputs keep row order).

    An indivisible batch errors HERE with the offending leaf spelled out,
    mirroring ``fabric.shard_batch``'s divisibility law — historically this
    class of mismatch surfaced as an opaque reshape error deep in XLA."""
    m = int(microbatches)

    def split(x: Any) -> Any:
        shape = jnp.shape(x)
        if len(shape) <= axis:
            raise ValueError(
                f"split_microbatches: leaf of shape {shape} has no axis {axis} to microbatch"
            )
        dim = shape[axis]
        if dim % m != 0:
            raise ValueError(
                f"pipeline: leaf of shape {shape} cannot split axis {axis} "
                f"({dim} rows) into {m} microbatches; batch sizes must be "
                f"multiples of pipeline.microbatches (the same divisibility "
                f"law as fabric.shard_batch's data axis)"
            )
        x = jnp.reshape(x, shape[:axis] + (m, dim // m) + shape[axis + 1:])
        return jnp.moveaxis(x, axis, 0)

    return jax.tree.map(split, tree)


def merge_microbatches(x: jax.Array, axis: int = 1) -> jax.Array:
    """Inverse of :func:`split_microbatches` for one stacked output:
    ``(M, ..., b, ...) → (..., M*b, ...)``."""
    x = jnp.moveaxis(x, 0, axis)
    shape = x.shape
    return jnp.reshape(x, shape[:axis] + (shape[axis] * shape[axis + 1],) + shape[axis + 2:])


def chunked_rows(fn: Callable[[jax.Array], jax.Array], x: jax.Array, chunks: int) -> jax.Array:
    """Apply a per-row ``fn`` over ``chunks`` row-chunks of ``x`` via
    ``lax.map`` — the microbatched form of the imagination batch's wide head
    evaluations (reward/value/continue over ``(H+1)·L·B`` rows).  Sequential
    chunks bound the live activation footprint to ``rows/chunks`` without
    changing any per-row value (parity is pure reassociation).  Indivisible
    row counts error with the same law as :func:`split_microbatches`."""
    c = int(chunks)
    if c <= 1:
        return fn(x)
    n = x.shape[0]
    if n % c != 0:
        raise ValueError(
            f"pipeline: imagination batch of {n} rows cannot split into "
            f"{c} chunks; pipeline.imagination_microbatches must divide the "
            f"(horizon+1)·L·B row count (the same divisibility law as "
            f"fabric.shard_batch's data axis)"
        )
    xs = jnp.reshape(x, (c, n // c) + x.shape[1:])
    ys = jax.lax.map(fn, xs)
    return jnp.reshape(ys, (n,) + ys.shape[2:])


# --------------------------------------------------------------------------
# the pipelined value-and-grad
# --------------------------------------------------------------------------

def pipeline_value_and_grad(
    stage_fns: Sequence[Callable[..., Any]],
    params: Any,
    consts: Any,
    *,
    microbatches: int,
    stage_names: Optional[Sequence[str]] = None,
    constrain: Optional[Callable[[int, Any], Any]] = None,
) -> Tuple[jax.Array, Any, Any]:
    """Run a linear stage chain over microbatches in 1F1B order and return
    ``(loss, aux_stacked, grads)``.

    ``stage_fns`` is the chain: ``stage_fns[0](params, None, const_m)`` →
    carry, middle stages ``(params, carry, const_m)`` → carry, and the LAST
    stage returns ``(loss_m, aux_m)`` (means over the microbatch — the
    returned ``loss``/``grads`` are microbatch means, equal to the
    full-batch values up to float reassociation because every dreamer loss
    is a batch mean).  ``consts`` is a pytree with leading microbatch axis
    ``M`` (data slices, pre-drawn noise — never differentiated).
    ``aux_stacked`` keeps the leading ``M`` axis; reassemble batch-shaped
    fields with :func:`merge_microbatches`.

    The schedule is unrolled at trace time inside the CALLER's jitted
    program — one executable per window signature (compile-once holds), the
    1F1B order realized as data dependencies: each backward unit folds its
    parameter cotangent into the running accumulator immediately, so the
    accumulation chain serializes backwards in schedule order and at most
    ``S - s`` forward residuals per stage are live (activation buffers are
    reused in place by XLA's donation-aware liveness).  ``constrain`` (e.g.
    a ``with_sharding_constraint`` over the ``data`` axis) is applied to
    every stage output so GSPMD keeps microbatch activations on their
    sub-groups."""
    S = len(stage_fns)
    M = int(microbatches)
    if S < 1:
        raise ValueError("pipeline_value_and_grad: need at least one stage")
    names = list(stage_names) if stage_names is not None else [f"stage{i}" for i in range(S)]
    if len(names) != S:
        raise ValueError(f"pipeline_value_and_grad: {len(names)} names for {S} stages")
    order = one_f_one_b(S, M)

    def const_of(m: int) -> Any:
        return jax.tree.map(operator.itemgetter(m), consts)

    carries: Dict[Tuple[int, int], Any] = {}
    vjps: Dict[Tuple[int, int], Callable[..., Any]] = {}
    dcarry: Dict[Tuple[int, int], Any] = {}  # cotangent INTO stage s's carry input
    losses: List[Any] = [None] * M
    auxes: List[Any] = [None] * M
    grads = jax.tree.map(jnp.zeros_like, params)

    for op, s, m in order:
        tag = f"pipeline.{names[s]}.{'fwd' if op == 'F' else 'bwd'}"
        const_m = const_of(m)
        if op == "F":
            cin = None if s == 0 else carries.pop((s - 1, m))
            with jax.named_scope(tag):
                if s == S - 1:
                    if s == 0:
                        out, vjp, aux = jax.vjp(
                            lambda p: stage_fns[s](p, None, const_m), params, has_aux=True
                        )
                    else:
                        out, vjp, aux = jax.vjp(
                            lambda p, c: stage_fns[s](p, c, const_m), params, cin, has_aux=True
                        )
                    losses[m], auxes[m] = out, aux
                elif s == 0:
                    out, vjp = jax.vjp(lambda p: stage_fns[s](p, None, const_m), params)
                else:
                    out, vjp = jax.vjp(lambda p, c: stage_fns[s](p, c, const_m), params, cin)
            if s < S - 1:
                if constrain is not None:
                    out = constrain(s, out)
                carries[(s, m)] = out
            vjps[(s, m)] = vjp
        else:
            with jax.named_scope(tag):
                if s == S - 1:
                    cots = vjps.pop((s, m))(jnp.ones((), jnp.result_type(losses[m])))
                else:
                    cots = vjps.pop((s, m))(dcarry.pop((s + 1, m)))
            dp = cots[0]
            if s > 0:
                dcarry[(s, m)] = cots[1]
            # immediate fold-in: the accumulation chain pins the 1F1B order
            grads = jax.tree.map(jnp.add, grads, dp)

    inv_m = 1.0 / float(M)
    grads = jax.tree.map(lambda g: g * jnp.asarray(inv_m, g.dtype), grads)
    loss = jnp.mean(jnp.stack(losses))
    aux_stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *auxes)
    return loss, aux_stacked, grads


# --------------------------------------------------------------------------
# sharding integration (parallel/sharding.py rule tables)
# --------------------------------------------------------------------------

def compose_pipeline_rules(
    rules: Sequence[Tuple[str, Any]],
    *,
    pipeline_axis: str = PIPELINE_AXIS,
    model_axis: str = "model",
    has_model: bool = True,
) -> Tuple[Tuple[str, Any], ...]:
    """Rewrite a partition-rule table for a mesh with a ``pipeline`` axis.

    Every ``model``-sharded weight dimension tiles over the
    ``(pipeline, model)`` axis product (or over ``pipeline`` alone when the
    mesh has no ``model`` axis): on a ``{data: D, pipeline: S, model: K}``
    mesh each sub-group owns a ``1/(S·K)`` slice of every stage's kernels —
    the GSPMD weight-placement half of the stage partition (the schedule
    half lives in :func:`pipeline_value_and_grad`).  Callable rule specs are
    wrapped so their RESULT is rewritten the same way; validation
    (axis-exists / dims-divide, ``sharding.undivisible`` policy) stays in
    ``partition_specs`` downstream."""

    def rewrite(spec: Optional[P]) -> Optional[P]:
        if spec is None:
            return None
        out: List[Any] = []
        for entry in spec:
            if entry == model_axis:
                out.append((pipeline_axis, model_axis) if has_model else pipeline_axis)
            elif isinstance(entry, (tuple, list)) and model_axis in entry:
                out.append((pipeline_axis, *entry))
            else:
                out.append(entry)
        return P(*out)

    composed: List[Tuple[str, Any]] = []
    for regex, spec in rules:
        if isinstance(spec, P) or spec is None:
            composed.append((regex, rewrite(spec)))
        elif callable(spec):
            def wrapped(path, leaf, mesh, _fn=spec):
                return rewrite(_fn(path, leaf, mesh))

            composed.append((regex, wrapped))
        else:
            composed.append((regex, spec))
    return tuple(composed)


def stage_batch_constraint(mesh: Any, data_axis: str, batch_axis: int = 1):
    """A ``constrain`` hook for :func:`pipeline_value_and_grad`: pin every
    stage output's microbatch batch axis to the ``data`` mesh axis so GSPMD
    keeps in-flight activations data-sharded on their sub-groups instead of
    round-tripping through a replicated layout between stages.  Leaves whose
    batch dim does not divide the axis pass through unconstrained (the
    ``shard_batch`` demotion rule)."""
    if mesh is None or data_axis not in getattr(mesh, "shape", {}):
        return None
    n = int(mesh.shape[data_axis])
    if n <= 1:
        return None

    def constrain(stage: int, carry: Any) -> Any:
        del stage

        def pin(x: Any) -> Any:
            if not hasattr(x, "ndim") or x.ndim <= batch_axis or x.shape[batch_axis] % n:
                return x
            spec = [None] * x.ndim
            spec[batch_axis] = data_axis
            return jax.lax.with_sharding_constraint(
                x, jax.sharding.NamedSharding(mesh, P(*spec))
            )

        return jax.tree.map(pin, carry)

    return constrain


# --------------------------------------------------------------------------
# per-stage measurement harness (no caller in the tree: ROADMAP D5)
# --------------------------------------------------------------------------

def compile_stage_pair(fabric: Any, stage_fn: Callable[[Any, Any], Any], *, name: str,
                       max_recompiles: Optional[int] = None) -> Tuple[Any, Any]:
    """Standalone compiled ``(forward, backward)`` programs for ONE stage —
    a per-stage timing harness (``Phase/pipeline.stage.*`` spans) that a
    pipeline cell would drive; nothing in the tree calls it (ROADMAP D5).

    The backward rematerializes the stage forward (the 1F1B activation-
    recompute discipline, same lever as ``algo.remat``) and DONATES both the
    inter-stage activation buffer and the incoming cotangent — after a
    stage's backward the activation is dead by construction.  Reading a
    donated activation again afterwards is exactly the hazard graftlint's
    ``use-after-donate`` rule flags (donation.py's curated table carries
    this factory), so keep the canonical rebinding shape at call sites:
    ``act = fwd(p, x); dx = bwd(p, act, dy)`` and rebind ``act`` before the
    next use."""

    def fwd(p, x):
        return stage_fn(p, x)

    def bwd(p, x, dy):
        _, vjp = jax.vjp(lambda xx: stage_fn(p, xx), x)
        (dx,) = vjp(dy)
        return dx

    fwd_c = fabric.compile(fwd, name=f"{name}.fwd", max_recompiles=max_recompiles)
    bwd_c = fabric.compile(
        bwd, name=f"{name}.bwd", donate_argnums=(1, 2), max_recompiles=max_recompiles
    )
    return fwd_c, bwd_c


# --------------------------------------------------------------------------
# telemetry
# --------------------------------------------------------------------------

def register_pipeline_metrics(spec: PipelineSpec) -> None:
    """Publish the schedule's shape as hub metrics (``Pipeline/stages``,
    ``Pipeline/microbatches``, ``Pipeline/bubble_frac``) — bubble fraction
    as a first-class metric next to the ``Phase/*`` fractions.  Re-register
    is the hub's documented supersede semantics (a new run's spec replaces
    the finished run's)."""
    from sheeprl_tpu.telemetry.hub import HUB

    HUB.register("pipeline", spec.metrics)
