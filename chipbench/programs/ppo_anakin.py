"""The fused Anakin PPO path (``ppo.anakin_phase``): what the harness probes, counts and captures.

Knows the program's names (executable, argument order), nothing of its code.  One dispatch
takes ``(params, opt_state, actor, key)`` and returns ``(params, opt_state, actor, key,
losses, episode_stats)``: a whole rollout and all of its epochs x minibatches updates.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from chipbench import compare, flops
from chipbench.harness import load_module

STEADY = "ppo.anakin_phase"
HOST_PROBES: Dict[str, str] = {}  # no host call of its own: the env, the player and the ring are inside the program
DEVICE_CALLS: frozenset = frozenset()  # which of the host probes dispatch device work


def before_window(snap: Dict[str, Any]) -> None:
    """One executable and one shape: nothing is left to warm."""


def is_steady(cfg: Dict[str, Any], args, kwargs) -> bool:
    return True


def _minibatches(cfg: Dict[str, Any]) -> int:
    frames = cfg["env"]["num_envs"] * cfg["algo"]["rollout_steps"]
    batch = min(int(cfg["algo"]["per_rank_batch_size"]), frames)
    return -(-frames // batch)


def work_per_iteration(cfg: Dict[str, Any]) -> Dict[str, int]:
    return {}


def work_per_call(cfg: Dict[str, Any], name: str, args, kwargs) -> Dict[str, int]:
    if name != STEADY:
        return {}
    return {
        "env_steps": cfg["env"]["num_envs"] * cfg["algo"]["rollout_steps"],
        "updates": cfg["algo"]["update_epochs"] * _minibatches(cfg),
    }


def flops_per_update(cfg: Dict[str, Any], shapes: flops.Shapes) -> float:
    a = cfg["algo"]
    return flops.ppo_fused(shapes, cfg["env"]["num_envs"], a["rollout_steps"], a["update_epochs"], _minibatches(cfg))


# ----------------------------------------------------------------------------
# what `correct` captures from the first dispatches
# ----------------------------------------------------------------------------

def observe(label: str, args, kwargs, out, snap: Dict[str, Any]) -> None:
    """Nothing outside the steady executable is compared in this path."""


def capture_inputs(args, kwargs, step: int) -> Dict[str, Any]:
    if step > 0:
        return {}  # the reference feeds itself from the first inputs on
    params, opt_state, actor, key = args
    env = actor["env"]
    return {
        "params": params, "adam": compare.adam_state(opt_state), "key": key,
        "actor": {"env": dict(env._asdict()), "ep_ret": actor["ep_ret"], "ep_len": actor["ep_len"], "update": actor["update"]},
    }


def capture_outputs(out) -> Dict[str, Any]:
    params, opt_state, _actor, _key, losses, stats = out
    return {"params": params, "adam": compare.adam_state(opt_state), "losses": tuple(losses), "stats": dict(stats)}


def param_shapes(inputs: Dict[str, Any]) -> flops.Shapes:
    return flops.shapes_of(inputs["params"])


# ----------------------------------------------------------------------------
# correct
# ----------------------------------------------------------------------------

def hyperparams(cfg: Dict[str, Any]) -> Dict[str, Any]:
    a = cfg["algo"]
    for flag in ("anneal_lr", "anneal_clip_coef", "anneal_ent_coef"):
        if a.get(flag):
            raise ValueError(f"the reference has no {flag}")
    if a["loss_reduction"] != "mean" or not a["clip_vloss"] or not a["normalize_advantages"]:
        raise ValueError("the reference is written for mean reduction, a clipped value loss and normalised advantages")
    frames = cfg["env"]["num_envs"] * a["rollout_steps"]
    betas = a["optimizer"].get("betas", [0.9, 0.999])
    return {
        "gamma": float(a["gamma"]), "gae_lambda": float(a["gae_lambda"]), "clip_coef": float(a["clip_coef"]),
        "vf_coef": float(a["vf_coef"]), "ent_coef": float(a["ent_coef"]), "max_grad_norm": float(a["max_grad_norm"]),
        "lr": float(a["optimizer"]["lr"]), "eps": float(a["optimizer"]["eps"]), "b1": float(betas[0]), "b2": float(betas[1]),
        "rollout_steps": int(a["rollout_steps"]), "update_epochs": int(a["update_epochs"]),
        "batch_size": min(int(a["per_rank_batch_size"]), frames), "num_minibatches": _minibatches(cfg),
    }


def follow(ref, inputs: Dict[str, Any], hp: Dict[str, Any], steps: int, precision: str = "f32", fault=None) -> List[Dict[str, Any]]:
    """The reference from the program's first inputs through ``steps`` dispatches, feeding itself."""
    import jax
    import jax.numpy as jnp

    static = tuple(sorted(hp.items()))
    params = jax.tree.map(jnp.asarray, inputs["params"])
    adam = jax.tree.map(jnp.asarray, inputs["adam"])
    mu, nu, count = adam["mu"], adam["nu"], adam["count"]
    actor = dict(inputs["actor"], env=ref.Room(**inputs["actor"]["env"]))
    actor = jax.tree.map(jnp.asarray, actor)
    key = jnp.asarray(inputs["key"])
    out = []
    with jax.default_matmul_precision("highest"):
        for _ in range(steps):
            params, mu, nu, count, actor, key, losses, stats = ref.dispatch(
                params, mu, nu, count, actor, key, hp_static=static, precision=precision, fault=fault
            )
            out.append(jax.device_get({
                "params": params, "adam": {"mu": mu, "nu": nu, "count": count}, "losses": tuple(losses), "stats": stats,
            }))
    return out


LOSS_FLOORS = (1e-2, 1e-4, 1e-1)  # policy (about nought at the start), value, entropy: the scale each gap is read on


def numbers(inputs: Dict[str, Any], got: List[Dict[str, Any]], ref: List[Dict[str, Any]]) -> Dict[str, float]:
    """The numbers compared: ``got`` (the program, or a control in its place) against the reference."""
    loss_gaps = [
        [compare.scalar_gap(p, r, floor) for p, r, floor in zip(g["losses"], f["losses"], LOSS_FLOORS)]
        for g, f in zip(got, ref)
    ]
    mu = compare.leaf_gaps(compare.leaf_norms(got[0]["adam"]["mu"]), compare.leaf_norms(ref[0]["adam"]["mu"]))
    # Adam's second moment after one dispatch is all but a plain sum of the twelve squared gradients
    # (b2 = 0.999), so it is steady where the first moment, which weighs the last updates most, is not;
    # and it is what half a batch moves: a gradient that is mostly sampling noise has twice the square
    nu = compare.leaf_gaps(compare.leaf_norms(got[0]["adam"]["nu"]), compare.leaf_norms(ref[0]["adam"]["nu"]))
    # leaves whose gradient is nought to rounding in the reference move by round-off alone
    skip = compare.tiny_gradient_leaves(compare.leaf_norms(ref[0]["adam"]["nu"]), share=1e-6)  # nu is gradient squared
    dp = compare.leaf_gaps(
        compare.change_norms(got[-1]["params"], inputs["params"]),
        compare.change_norms(ref[-1]["params"], inputs["params"]), skip=skip,
    )
    # Half of every minibatch left out is another sample of the same gradient: it turns each step without
    # changing its length much, so the gaps of norms above read it at second order, no further from the
    # reference than the rounding that twelve Adam steps amplify.  The norm of the difference reads it at
    # first order; the median leaf, after the first dispatch (the least amplified), is steady from seed to seed.
    first = compare.leaf_diffs(
        compare.tree_sub(got[0]["params"], inputs["params"]), compare.tree_sub(ref[0]["params"], inputs["params"]), skip=skip)
    nu_diff = compare.leaf_diffs(got[0]["adam"]["nu"], ref[0]["adam"]["nu"])
    gs, rs = got[0]["stats"], ref[0]["stats"]
    differs = (
        (np.asarray(gs["ep_done"]) != np.asarray(rs["ep_done"]))
        | (np.asarray(gs["ep_len"]) != np.asarray(rs["ep_len"]))
        | (np.abs(np.asarray(gs["ep_ret"], np.float64) - np.asarray(rs["ep_ret"], np.float64)) > 1e-5)
    )
    return {
        # the first dispatch's losses are steady from seed to seed; the later ones carry 24 and 36 updates of amplified
        # rounding and swing (PERF.md section 2), so they are reported and not compared
        "first_loss_gap": max(loss_gaps[0]), "loss_gap": max(max(row) for row in loss_gaps), "moment_gap": max(mu.values()),
        "second_moment_gap": max(nu.values()), "change_gap": max(dp.values()),
        "first_change_diff": float(np.median(list(first.values()))),
        "second_moment_diff": float(np.median(list(nu_diff.values()))),
        "episode_gap": float(differs.any(axis=0).mean()),
        "_where": {"loss_gaps": loss_gaps, "moment_gap": compare.worst_few(mu), "change_gap": compare.worst_few(dp),
                   "second_moment_gap": compare.worst_few(nu), "second_moment_median": float(np.median(list(nu.values()))),
                   "moment_median": float(np.median(list(mu.values()))), "change_median": float(np.median(list(dp.values()))),
                   "first_change_diff": compare.worst_few(first), "skipped": skip},
    }


def _reference(cfg: Dict[str, Any], snap: Dict[str, Any], config_file: Dict[str, Any]):
    """The reference's dispatches, worked out once for a set of captured inputs (the stand-ins share them)."""
    if "_reference" not in snap:
        ref_mod = load_module("reference", config_file["reference"])
        snap["_reference"] = follow(ref_mod, snap["inputs"][0], hyperparams(cfg), len(snap["outputs"]))
    return snap["_reference"]


def check(cfg: Dict[str, Any], snap: Dict[str, Any], config_file: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """{name: {"value", "limit"}} for the numbers that decide `correct`, and without a limit what says where a gap sits."""
    reference = _reference(cfg, snap, config_file)
    limits = config_file["limits"]
    got = numbers(snap["inputs"][0], snap["outputs"], reference)
    out: Dict[str, Dict[str, Any]] = {k: {"value": got[k], "limit": limits[k]} for k in limits}
    out["where"] = {"value": got["_where"]}
    out["more"] = {"value": {k: v for k, v in got.items() if k not in limits and not k.startswith("_")}}
    out["losses"] = {"value": {"program": [list(map(float, g["losses"])) for g in snap["outputs"]],
                               "reference": [list(map(float, r["losses"])) for r in reference]}}
    return out


def stand_in(cfg: Dict[str, Any], snap: Dict[str, Any], config_file: Dict[str, Any], name: str) -> Dict[str, Any]:
    """What the probes would have copied had ``name`` stood in the program's place: ``control``, the reference in the
    precision below the configuration's, or a fault the reference plants (``half_batch``), from the same inputs."""
    _reference(cfg, snap, config_file)
    ref_mod = load_module("reference", config_file["reference"])
    how = {"precision": config_file["control_precision"]} if name == "control" else {"fault": name}
    other = follow(ref_mod, snap["inputs"][0], hyperparams(cfg), len(snap["outputs"]), **how)
    return dict(snap, outputs=other)
