"""In-trace population-based training on the Anakin axis.

The Podracer observation (arXiv:2104.06272) is that a fused Anakin program
leaves one axis spare: ``jax.vmap`` over WHOLE agents — params, opt-state,
per-member env shards and hyperparameters-as-data — turns single-agent
training into population training at the cost of one (bigger) executable,
not N processes.  This module supplies everything algo loops need to do
that, plus in-trace PBT (Jaderberg et al., arXiv:1711.09846):

* **hyperparameters as data** — lr / ent_coef / clip_coef live as ``(P,)``
  device arrays.  The optimizer factory injects every hyperparameter
  (``optax.inject_hyperparams``, utils/optim.py), so a traced per-member lr
  drops straight into the opt-state; clip/ent enter the loss as traced
  arguments.  PR 11's annealing-as-traced-data machinery proved the trick.
* **fitness from the carry** — the Anakin rollout already accumulates
  per-step episode completions (``ep_done``/``ep_ret``); an EMA over each
  member's finished-episode returns is the PBT fitness, computed in-trace
  with zero extra env interaction.
* **exploit/explore without ``lax.cond``** — selection is gated on the
  donated update counter with pure ``jnp.where`` selects: truncation
  selection copies params AND opt-state together from the top members onto
  the bottom members (a ``jnp.take`` gather with a per-member source index
  that is the identity when the gate is closed), then perturbs the copied
  members' hyperparameters by a seeded log-uniform factor.  One trace, one
  executable: ``cache_size()==1`` holds across the whole run and the
  steady state stays zero-H2D under the armed transfer guard.

The difficulty curriculum rides the same axis: every jax env exposes an
``env.level`` knob (docs/jax_envs.md) and the traced-level envs (cartpole,
pendulum, multiroom) carry it as a state leaf, so
:func:`apply_level_curriculum` can pin DIFFERENT difficulties to different
members inside the one executable.  See docs/population.md.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class PBTConfig:
    """Validated snapshot of the ``population`` config group (plus the
    algo's base hyperparameter values the run would use at population=1)."""

    size: int
    exploit_every: int
    warmup: int
    frac: float
    perturb_min: float
    perturb_max: float
    init_min: float
    init_max: float
    bound_min: float
    bound_max: float
    fitness_alpha: float
    levels: Optional[List[float]]
    base: Dict[str, float]

    @classmethod
    def from_cfg(cls, cfg: Any, base: Dict[str, float]) -> "PBTConfig":
        pop = cfg.population
        levels = pop.get("levels")
        self = cls(
            size=int(pop.size),
            exploit_every=int(pop.exploit_every),
            warmup=int(pop.warmup),
            frac=float(pop.frac),
            perturb_min=float(pop.perturb_min),
            perturb_max=float(pop.perturb_max),
            init_min=float(pop.init_min),
            init_max=float(pop.init_max),
            bound_min=float(pop.bound_min),
            bound_max=float(pop.bound_max),
            fitness_alpha=float(pop.fitness_alpha),
            levels=[float(x) for x in levels] if levels else None,
            base={k: float(v) for k, v in base.items()},
        )
        if self.size < 2:
            raise ValueError(f"population.size must be >= 2 to train a population (got {self.size})")
        if not 0.0 < self.frac <= 0.5:
            raise ValueError(f"population.frac must be in (0, 0.5] (got {self.frac})")
        if not 0.0 < self.perturb_min <= self.perturb_max:
            raise ValueError("population.perturb_min/max must satisfy 0 < min <= max")
        if not 0.0 < self.init_min <= self.init_max:
            raise ValueError("population.init_min/max must satisfy 0 < min <= max")
        if not 0.0 < self.bound_min <= self.bound_max:
            raise ValueError("population.bound_min/max must satisfy 0 < min <= max")
        if not 0.0 < self.fitness_alpha <= 1.0:
            raise ValueError("population.fitness_alpha must be in (0, 1]")
        return self

    @property
    def n_select(self) -> int:
        """Truncation width: how many bottom members copy from the top —
        STATIC (shapes one gather), clamped to [1, size // 2]."""
        return max(1, min(self.size // 2, int(round(self.frac * self.size))))

    # -- seeded initial hyperparameter spread --------------------------------
    def init_hyperparams(self, key: jax.Array) -> Dict[str, jax.Array]:
        """Per-member ``(P,)`` arrays: base value × log-uniform factor in
        ``[init_min, init_max]``, clipped to the exploration bounds.  Key
        derivation is positional over the sorted hyperparameter names, so
        the spread is reproducible per seed."""
        hp: Dict[str, jax.Array] = {}
        for i, name in enumerate(sorted(self.base)):
            k = jax.random.fold_in(key, i)
            factor = jnp.exp(
                jax.random.uniform(
                    k, (self.size,),
                    minval=jnp.log(self.init_min), maxval=jnp.log(self.init_max),
                )
            )
            base = self.base[name]
            hp[name] = jnp.clip(
                jnp.float32(base) * factor, base * self.bound_min, base * self.bound_max
            )
        return hp


def tile_stack(tree: Any, size: int) -> Any:
    """Stack ``size`` copies of a pytree along a new leading population
    axis — the fresh-start member params (all members start at the same
    init; the hyperparameter spread is what diversifies them)."""
    return jax.tree.map(lambda x: jnp.stack([x] * size), tree)


def apply_level_curriculum(env_state: Any, levels: List[float], size: int, num_envs: int) -> Any:
    """Pin per-member difficulty levels onto a ``(P, B)``-batched env state.

    Member ``m`` trains at ``levels[m % len(levels)]``; envs carry the level
    as a traced state leaf, and auto-reset preserves the CARRIED level
    (envs/jax/core.py), so the override holds for the whole run.  Raises
    for level-less env states (e.g. forage, whose level is a static shape)
    rather than silently training a flat population.
    """
    if not hasattr(env_state, "level"):
        raise ValueError(
            "population.levels needs an env whose state carries a traced 'level' "
            "leaf (cartpole/pendulum/multiroom); static-level envs (forage) scale "
            "difficulty at construction via env.level instead"
        )
    per_member = jnp.asarray([levels[m % len(levels)] for m in range(size)], jnp.float32)
    return env_state._replace(level=jnp.broadcast_to(per_member[:, None], (size, num_envs)))


def pbt_exploit_explore(
    params: Any,
    opt_state: Any,
    hp: Dict[str, jax.Array],
    fitness: jax.Array,
    do_exploit: jax.Array,
    key: jax.Array,
    pbt: PBTConfig,
):
    """One gated truncation-selection + perturbation step, branch-free.

    ``do_exploit`` is a traced bool (derived from the donated update
    counter); everything below is ``jnp.argsort``/``take``/``where`` — no
    ``lax.cond``, no host sync — so the fused executable keeps ONE cache
    entry whether or not this window exploits.

    * exploit: the ``n_select`` worst members' source index points at the
      ``n_select`` best (worst←best, 2nd-worst←2nd-best, …); everyone else
      points at themselves.  Params and opt-state gather through the SAME
      index, so a copied member gets a coherent (weights, optimizer-moments)
      pair, and the copied member inherits the source's fitness (its old
      score described weights that no longer exist).
    * explore: members whose source differs from themselves perturb every
      hyperparameter by an independent seeded log-uniform factor in
      ``[perturb_min, perturb_max]``, clipped to ``base × [bound_min,
      bound_max]``.

    Returns ``(params, opt_state, hp, fitness, n_copied)`` with ``n_copied``
    the number of members overwritten this call (0 when gated off).
    """
    size, n = pbt.size, pbt.n_select
    idx = jnp.arange(size)
    order = jnp.argsort(fitness)  # ascending: worst first, best last
    # worst i copies best i: order[:n] ← reversed(order[-n:])
    src = idx.at[order[:n]].set(order[size - n :][::-1])
    src = jnp.where(do_exploit, src, idx)
    params = jax.tree.map(lambda x: jnp.take(x, src, axis=0), params)
    opt_state = jax.tree.map(lambda x: jnp.take(x, src, axis=0), opt_state)
    fitness = jnp.take(fitness, src)
    copied = src != idx
    new_hp: Dict[str, jax.Array] = {}
    for i, name in enumerate(sorted(hp)):
        k = jax.random.fold_in(key, i)
        factor = jnp.exp(
            jax.random.uniform(
                k, (size,), minval=jnp.log(pbt.perturb_min), maxval=jnp.log(pbt.perturb_max)
            )
        )
        v = jnp.take(hp[name], src) * jnp.where(copied, factor, 1.0)
        base = pbt.base[name]
        new_hp[name] = jnp.clip(v, base * pbt.bound_min, base * pbt.bound_max)
    n_copied = jnp.where(do_exploit, jnp.int32(n), jnp.int32(0))
    return params, opt_state, new_hp, fitness, n_copied


def init_population_state(members: Dict[str, Any], pbt: PBTConfig, num_envs: int) -> Dict[str, Any]:
    """The population carry around the vmapped member actors: EMA fitness,
    the finished-episode counter that gates the EMA's first observation,
    and the running exploit-event count (all donated alongside the
    members)."""
    if pbt.levels:
        members = dict(members)
        members["env"] = apply_level_curriculum(members["env"], pbt.levels, pbt.size, num_envs)
    return {
        "members": members,
        "fitness": jnp.zeros((pbt.size,), jnp.float32),
        "ep_count": jnp.zeros((pbt.size,), jnp.int32),
        "exploits": jnp.zeros((), jnp.int32),
    }


def make_population_phase(member_phase: Callable, pbt: PBTConfig) -> Callable:
    """Wrap an algo's single-member fused phase into the population phase.

    ``member_phase(p, o_state, actor, key, hp) -> (p, o_state, actor,
    losses, stats)`` is the algo's Anakin rollout+train for ONE member with
    its hyperparameters as traced scalars (``hp`` maps name → scalar).
    The wrapper vmaps it over the population axis, folds the window's
    episode completions into the fitness EMA, and applies the gated PBT
    step — all inside whatever ``fabric.compile`` the caller wraps the
    result in, so the WHOLE population trains in one donated-carry
    executable.

    Returns ``population_phase(params, opt_state, pop, hp, key) ->
    (params, opt_state, pop, hp, key_next, losses, stats)`` where every
    pytree keeps its leading ``(P,)`` axis (losses/stats included — the
    loop reduces for logging).
    """

    def population_phase(params: Any, opt_state: Any, pop: Dict[str, Any], hp: Dict[str, jax.Array], key: jax.Array):
        k_members, k_pbt, k_next = jax.random.split(key, 3)
        member_keys = jax.random.split(k_members, pbt.size)
        params, opt_state, members, losses, stats = jax.vmap(member_phase)(
            params, opt_state, pop["members"], member_keys, hp
        )
        # -- fitness: EMA over each member's finished-episode mean return --
        done = stats["ep_done"].astype(jnp.float32)  # (P, T, B)
        n_done = done.sum(axis=(1, 2))
        mean_ret = (stats["ep_ret"] * done).sum(axis=(1, 2)) / jnp.maximum(n_done, 1.0)
        has_episodes = n_done > 0
        seen_before = pop["ep_count"] > 0
        ema = pbt.fitness_alpha * mean_ret + (1.0 - pbt.fitness_alpha) * pop["fitness"]
        # first observation seeds the EMA directly (an EMA from 0 would
        # bias early selection toward pessimism); no-completion windows
        # leave the score untouched
        fitness = jnp.where(has_episodes, jnp.where(seen_before, ema, mean_ret), pop["fitness"])
        ep_count = pop["ep_count"] + n_done.astype(jnp.int32)

        # -- gated exploit/explore on the donated update counter --
        exploits = pop["exploits"]
        if pbt.exploit_every > 0:  # static: exploit_every=0 removes PBT from the trace
            update = members["update"][0]  # members advance in lockstep
            do_exploit = (update > pbt.warmup) & (update % pbt.exploit_every == 0)
            params, opt_state, hp, fitness, n_copied = pbt_exploit_explore(
                params, opt_state, hp, fitness, do_exploit, k_pbt, pbt
            )
            exploits = exploits + n_copied
        new_pop = {"members": members, "fitness": fitness, "ep_count": ep_count, "exploits": exploits}
        return params, opt_state, new_pop, hp, k_next, losses, stats

    return population_phase


class PopulationMonitor:
    """``Population/*`` telemetry-hub source (hub contract: telemetry/hub.py).

    The loop feeds it host copies of the fitness vector, hyperparameter
    arrays and exploit counter on its logging cadence (D2H pulls — legal
    under the H2D-scoped steady guard, like the episode stats); flushes
    report member fitness spread, cumulative exploit events and the
    hyperparameter quantiles the run is currently exploring.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._fitness: Optional[np.ndarray] = None
        self._hp: Dict[str, np.ndarray] = {}
        self._exploits = 0

    def observe(self, fitness: Any, hp: Dict[str, Any], exploits: Any) -> None:
        with self._lock:
            self._fitness = np.asarray(fitness, np.float64)
            self._hp = {k: np.asarray(v, np.float64) for k, v in hp.items()}
            self._exploits = int(exploits)

    def metrics(self) -> Dict[str, float]:
        with self._lock:
            fitness, hp, exploits = self._fitness, self._hp, self._exploits
        if fitness is None:
            return {}
        out = {
            "Population/fitness_best": float(fitness.max()),
            "Population/fitness_worst": float(fitness.min()),
            "Population/fitness_spread": float(fitness.max() - fitness.min()),
            "Population/exploit_events": float(exploits),
        }
        for name, values in hp.items():
            out[f"Population/{name}_p10"] = float(np.quantile(values, 0.10))
            out[f"Population/{name}_p50"] = float(np.quantile(values, 0.50))
            out[f"Population/{name}_p90"] = float(np.quantile(values, 0.90))
        return out


def write_population_summary(
    log_dir: str,
    pop: Dict[str, Any],
    hp: Dict[str, jax.Array],
    policy_step: int,
) -> str:
    """Land the run's final population snapshot as
    ``<log_dir>/population_summary.json`` — the machine-readable artifact
    ``tests/population_drill.py`` (run_ci's PBT drill) reads to compare
    members across runs."""
    fitness = np.asarray(pop["fitness"], np.float64)
    summary = {
        "policy_step": int(policy_step),
        "fitness": [float(x) for x in fitness],
        "best_member": int(fitness.argmax()),
        "worst_member": int(fitness.argmin()),
        "best_fitness": float(fitness.max()),
        "worst_fitness": float(fitness.min()),
        "episodes_per_member": [int(x) for x in np.asarray(pop["ep_count"])],
        "exploit_events": int(np.asarray(pop["exploits"])),
        "hyperparams": {k: [float(x) for x in np.asarray(v)] for k, v in sorted(hp.items())},
    }
    path = os.path.join(log_dir, "population_summary.json")
    with open(path, "w") as fh:
        json.dump(summary, fh, indent=2)
    return path
