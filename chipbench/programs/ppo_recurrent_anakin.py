"""The fused recurrent PPO path with the decoder core (``ppo_recurrent.anakin_phase``): what the harness
probes, counts and captures.

Knows the program's names (executable, argument order, the keys of what a dispatch returns), nothing of
its code.  One dispatch takes ``(params, opt_state, actor, key)`` and returns ``(params, opt_state,
actor, key, losses, stats)``: 256 decode steps of every env through the caches of the recurrent carry,
then the minibatch updates.  ``stats`` holds, beside the episode counts, the rollout as the caches
produced it (tokens, actions, log-probabilities, values, rewards, resets, loss mask), the first
minibatch's losses and the router's counts: what ``correct`` compares is what the timed executable
produced at the timed sizes.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from chipbench import compare, flops, flops_decoder
from chipbench.harness import WARMUP_DISPATCHES, load_module

STEADY = "ppo_recurrent.anakin_phase"
HOST_PROBES: Dict[str, str] = {}  # no host call of its own: the env and the policy's caches are inside the program
DEVICE_CALLS: frozenset = frozenset()
ROLLOUT_KEYS = ("tokens", "actions", "logprobs", "values", "rewards", "dones", "is_first", "mask",
                "next_tokens", "next_is_first")
GROUP = 4  # envs the reference runs at a time: the blocks needed to fit
_dispatches = [0]  # steady dispatches captured so far in this run (capture_outputs is not told which it is)


def before_window(snap: Dict[str, Any]) -> None:
    """One executable and one shape: nothing is left to warm."""


def is_steady(cfg: Dict[str, Any], args, kwargs) -> bool:
    return True


def _env_bs(cfg: Dict[str, Any]) -> int:
    a = cfg["algo"]
    return max(1, min(cfg["env"]["num_envs"], int(a["per_rank_batch_size"]) // int(a["rollout_steps"])))


def _minibatches(cfg: Dict[str, Any]) -> int:
    return -(-cfg["env"]["num_envs"] // _env_bs(cfg))


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
    a, w = cfg["algo"], cfg["env"]["wrapper"]
    return flops_decoder.ppo_decoder(
        shapes, a["decoder"], cfg["env"]["num_envs"] * a["rollout_steps"], a["update_epochs"], _minibatches(cfg),
        w["len_min"], w["len_max"],
    )


# ----------------------------------------------------------------------------
# what `correct` captures from the first dispatches
# ----------------------------------------------------------------------------

def observe(label: str, args, kwargs, out, snap: Dict[str, Any]) -> None:
    """Nothing outside the steady executable is compared in this path."""


def capture_inputs(args, kwargs, step: int) -> Dict[str, Any]:
    if step > 0:
        return {}  # the reference feeds itself from the first inputs on
    _dispatches[0] = 0
    params, opt_state, actor, key = args
    import jax
    import jax.numpy as jnp

    adam = compare.adam_state(opt_state)
    largest = lambda tree: jnp.max(jnp.stack([jnp.max(jnp.abs(x)) for x in jax.tree.leaves(tree)]))  # noqa: E731
    return {  # Adam's moments are 5.6 GB on the host and nought in a run's first dispatch: their largest entry says so
        "params": params["params"], "adam": {"count": adam["count"], "mu_max": largest(adam["mu"]), "nu_max": largest(adam["nu"])},
        "key": key, "env": dict(actor["env"]._asdict()), "pos": actor["carry"]["pos"], "is_first": actor["is_first"],
    }


def _tree(adam_leaf: Any) -> Any:
    return adam_leaf["params"] if isinstance(adam_leaf, dict) and "params" in adam_leaf else adam_leaf


def _norms(tree: Any) -> Any:
    """Per leaf, the norm, worked out where the tree lives: a tree of scalars comes to the host, not 2.8 GB."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def capture_outputs(out) -> Dict[str, Any]:
    """The rollout and the counts of every dispatch; the norms of Adam's first moment after the first and
    the parameters after the last only (2.8 GB on the host)."""
    params, opt_state, _actor, _key, losses, stats = out
    step = _dispatches[0]
    _dispatches[0] += 1
    got = {k: stats[k] for k in ROLLOUT_KEYS + ("first_losses", "first_load", "load")}
    got["losses"] = tuple(losses)
    if step == 0:
        got["mu_norms"] = _norms(_tree(compare.adam_state(opt_state)["mu"]))
    got["params"] = params["params"] if step == WARMUP_DISPATCHES - 1 else None
    return got


def param_shapes(inputs: Dict[str, Any]) -> flops.Shapes:
    return flops.shapes_of(inputs["params"])


# ----------------------------------------------------------------------------
# correct
# ----------------------------------------------------------------------------

def hyperparams(cfg: Dict[str, Any]) -> Dict[str, Any]:
    a = cfg["algo"]
    for flag in ("anneal_lr", "anneal_ent_coef", "clip_vloss", "normalize_advantages"):
        if a.get(flag):
            raise ValueError(f"the reference has no {flag}")
    if a["loss_reduction"] != "mean" or a["optimizer"]["name"] != "adamw":
        raise ValueError("the reference is written for mean reduction and AdamW")
    if cfg["env"]["num_envs"] % _env_bs(cfg):
        raise ValueError("the reference is written for minibatches that divide the envs")
    return {
        "gamma": float(a["gamma"]), "gae_lambda": float(a["gae_lambda"]), "clip_coef": float(a["clip_coef"]),
        "vf_coef": float(a["vf_coef"]), "ent_coef": float(a["ent_coef"]), "max_grad_norm": float(a["max_grad_norm"]),
        "lr": float(a["optimizer"]["lr"]), "eps": float(a["optimizer"]["eps"]), "b1": 0.9, "b2": 0.999,
        "weight_decay": float(a["optimizer"]["weight_decay"]), "update_epochs": int(a["update_epochs"]),
        "env_bs": _env_bs(cfg), "num_minibatches": _minibatches(cfg), "len_max": int(cfg["env"]["wrapper"]["len_max"]),
    }


def model_config(cfg: Dict[str, Any]) -> Dict[str, Any]:
    d = dict(cfg["algo"]["decoder"])
    d["layer_types"], d["experts_held"] = tuple(d["layer_types"]), tuple(d["experts_held"])
    return d


def _by_group(fn, n: int, *trees):
    """``fn`` over the env axis (leading) of ``trees``, ``GROUP`` envs at a time; results concatenated on the host."""
    import jax

    parts = []
    for g in range(0, n, GROUP):
        parts.append(jax.device_get(fn(*jax.tree.map(lambda z: z[g:g + GROUP], trees))))
    return jax.tree.map(lambda *zs: np.concatenate(zs, axis=0), *parts)


def follow(ref, inputs: Dict[str, Any], rollouts: List[Dict[str, Any]], hp: Dict[str, Any], model: Dict[str, Any],
           precision: str = "f32", fault=None) -> List[Dict[str, Any]]:
    """The reference through the dispatches whose rollouts the program sampled (``rollouts``: tokens, actions,
    rewards, resets, mask of each), from the program's first parameters, Adam state and key, feeding itself."""
    import jax
    import jax.numpy as jnp

    static_hp = ref._Static(hp)
    see_all = np.bool_(fault == "window")  # a flag of the one program, so that this fault costs no compile of its own
    fault = None if fault == "window" else fault
    how = {"cfg": ref._Static(model), "precision": precision, "fault": fault}
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(jnp.asarray, inputs["params"])
        adam = inputs["adam"]
        if float(adam["mu_max"]) != 0.0 or float(adam["nu_max"]) != 0.0 or int(adam["count"]) != 0:
            raise ValueError("the reference follows a run from its first update: Adam's state was not nought")
        mu, nu, count = jax.tree.map(jnp.zeros_like, params), jax.tree.map(jnp.zeros_like, params), jnp.asarray(adam["count"])
        key = jnp.asarray(inputs["key"])
        env = inputs["env"]
        n = np.asarray(env["t"], np.int32)  # steps each env's episode has behind it: the history the caches were filled from
        if not np.array_equal(n, np.asarray(inputs["pos"])):
            raise ValueError("the carry's positions are not the envs' steps: the caches do not hold the episodes so far")
        B, T = n.shape[0], np.asarray(rollouts[0]["tokens"]).shape[0]
        P0 = -(-int(hp["len_max"]) // T) * T  # room for the longest episode so far: one shape whatever the seed drew
        P = P0 + len(rollouts) * T
        KV, D, layers = model["num_key_value_heads"], model["head_dim"], len(model["layer_types"])
        past = {  # on the host; ep -1: nothing there
            "k": [np.zeros((B, P, KV, D), np.float32) for _ in range(layers)],
            "v": [np.zeros((B, P, KV, D), np.float32) for _ in range(layers)],
            "pos": np.zeros((B, P), np.int32), "ep": np.full((B, P), -1, np.int32),
        }
        # the episodes so far under the first parameters (what the program's prefill cached), a rollout's length at a time
        hist = np.asarray(ref.history(jnp.asarray(env["prompt"]), jnp.asarray(env["prompt_len"]), jnp.asarray(n), P0))
        for g in range(0, B, GROUP):
            rows = slice(g, g + GROUP)
            group = jax.tree.map(lambda z: jnp.asarray(z[rows]), past)
            for lo in range(0, int(n[rows].max()), T):
                h_pos = np.broadcast_to(np.arange(lo, lo + T, dtype=np.int32), (n[rows].shape[0], T))
                h_ep = np.where(h_pos < n[rows, None], 0, -1).astype(np.int32)
                made = ref.forward_jit(params, hist[rows, lo:lo + T], h_pos, h_ep, group, see_all, **how)[3]
                group = ref.extend_past(group, made, h_pos, h_ep, lo)
            for host, dev in zip(jax.tree.leaves(past), jax.tree.leaves(group)):
                host[rows] = np.asarray(dev)
        pos0, ep0 = jnp.asarray(n), jnp.zeros((B,), jnp.int32)
        out = []
        for d, roll in enumerate(rollouts):
            tokens = np.asarray(roll["tokens"])[..., 0].astype(np.int32).T  # (B, T)
            first = np.asarray(roll["is_first"])[..., 0]
            pos, ep = (np.asarray(z).T for z in ref.positions(jnp.asarray(first), pos0, ep0))
            logits, values, _, made = _by_group(
                lambda tok, p_, e_, pa: ref.forward_jit(params, tok, p_, e_, pa, see_all, **how), B, tokens, pos, ep, past)
            actions = np.asarray(roll["actions"])[..., 0].astype(np.int32).T
            logp_all = np.asarray(jax.nn.log_softmax(jnp.asarray(logits), axis=-1))
            logp = np.take_along_axis(logp_all, actions[..., None], axis=-1)[..., 0]
            # the value after the last step: one more token on the past and this rollout
            lo = P0 + d * T
            # written in place (5.9 GB at the cell's size): `past` keeps the old `ep`, under which the new columns hold nothing
            after = dict(past, pos=past["pos"].copy(), ep=past["ep"].copy())
            for i, (k, v) in enumerate(made):
                after["k"][i][:, lo:lo + T], after["v"][i][:, lo:lo + T] = k, v
            after["pos"][:, lo:lo + T], after["ep"][:, lo:lo + T] = pos, ep
            n_first = np.asarray(roll["next_is_first"])
            n_pos, n_ep = (np.asarray(z).T for z in ref.positions(
                jnp.asarray(n_first.reshape(1, B)), jnp.asarray(pos[:, -1] + 1), jnp.asarray(ep[:, -1])))
            # ... through the rollout's own shape (the one program compiled): the token first, padding after it
            pad = lambda z, fill: np.concatenate([z, np.full((B, T - 1), fill, np.int32)], axis=1)  # noqa: E731
            n_tok = pad(np.asarray(roll["next_tokens"]).reshape(B, 1).astype(np.int32), 0)
            last_v = _by_group(
                lambda tok, p_, e_, pa: ref.forward_jit(params, tok, p_, e_, pa, see_all, **how)[1],
                B, n_tok, pad(n_pos, 0), pad(n_ep, -1), after)[:, 0]
            returns, adv = ref.gae(
                jnp.asarray(roll["rewards"]), jnp.asarray(values.T), jnp.asarray(roll["dones"]), jnp.asarray(last_v),
                hp["gamma"], hp["gae_lambda"])
            mask = np.asarray(roll["mask"]).T
            whole = {"tokens": tokens, "pos": pos, "ep": ep, "actions": actions, "old_logp": logp,
                     "advantages": np.asarray(adv).T, "returns": np.asarray(returns).T, "mask": mask}
            _k_roll, k_train, key = jax.random.split(key, 3)
            record: Dict[str, Any] = {"logprobs": logp.T, "values": values.T}
            load = 0
            for e, k_e in enumerate(jax.random.split(k_train, hp["update_epochs"])):
                perm = np.asarray(jax.random.permutation(k_e, B))
                for i in range(hp["num_minibatches"]):
                    idx = perm[i * hp["env_bs"]:(i + 1) * hp["env_bs"]]
                    take = lambda z: z[idx]  # noqa: E731
                    params, mu, nu, count, losses, counts = ref.update(
                        params, mu, nu, count, jax.tree.map(take, whole), jax.tree.map(take, past), see_all,
                        hp=static_hp, **how)
                    load = load + np.asarray(counts)
                    if e == 0 and i == 0:
                        record.update(first_losses=np.asarray(losses), first_load=np.asarray(counts))
            record.update(losses=tuple(np.asarray(losses)), load=load)
            if d == 0:
                record["mu_norms"] = jax.device_get(ref.leaf_norms(mu))
            if d == len(rollouts) - 1:
                record["change_norms"] = jax.device_get(ref.change_norms(params, jax.tree.map(jnp.asarray, inputs["params"])))
            out.append(record)
            # the next dispatch's first step resets where next_is_first says: `positions` applies it there
            past, pos0, ep0 = after, jnp.asarray(pos[:, -1] + 1), jnp.asarray(ep[:, -1])
    return out


LOSS_FLOORS = (1e-2, 1e-4, 1e-1)  # policy (about nought at the start), value, entropy: the scale each gap is read on


def _rms_gap(got, ref, weights=None) -> float:
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    scale = max(float(ref.std()), 1e-6)
    gap = float(np.sqrt(np.mean((got - ref) ** 2))) / scale
    return gap if np.isfinite(gap) else float("inf")


def numbers(inputs: Dict[str, Any], got: List[Dict[str, Any]], ref: List[Dict[str, Any]]) -> Dict[str, Any]:
    """The numbers compared: ``got`` (the program, or a control in its place) against the reference."""
    first_loss = [compare.scalar_gap(p, r, floor) for p, r, floor in zip(got[0]["first_losses"], ref[0]["first_losses"], LOSS_FLOORS)]
    # a tree of norms (each a scalar) reads as itself under `leaf_norms`
    mu = compare.leaf_gaps(compare.leaf_norms(got[0]["mu_norms"]), compare.leaf_norms(ref[0]["mu_norms"]))
    skip = compare.tiny_gradient_leaves(compare.leaf_norms(ref[0]["mu_norms"]), share=1e-6)  # the selection bias: no gradient
    change = lambda d: (compare.leaf_norms(d["change_norms"]) if "change_norms" in d  # noqa: E731
                        else compare.change_norms(d["params"], inputs["params"]))
    dp = compare.leaf_gaps(change(got[-1]), change(ref[-1]), skip=skip)
    load_g, load_r = np.asarray(got[0]["first_load"], np.float64), np.asarray(ref[0]["first_load"], np.float64)
    return {
        # the rollout's log-probabilities and values through the caches against the full forward on the same tokens
        "logprob_gap": _rms_gap(got[0]["logprobs"], ref[0]["logprobs"]),
        "value_gap": _rms_gap(got[0]["values"], ref[0]["values"]),
        "first_loss_gap": max(first_loss), "moment_gap": max(mu.values()), "change_gap": max(dp.values()),
        "load_gap": float(np.abs(load_g - load_r).sum() / max(load_r.sum(), 1.0)),
        "_where": {
            "first_loss_gaps": first_loss, "moment_gap": compare.worst_few(mu), "change_gap": compare.worst_few(dp),
            "moment_median": float(np.median(list(mu.values()))), "change_median": float(np.median(list(dp.values()))),
            "later_logprob_gaps": [_rms_gap(g["logprobs"], r["logprobs"]) for g, r in zip(got[1:], ref[1:])],
            "later_value_gaps": [_rms_gap(g["values"], r["values"]) for g, r in zip(got[1:], ref[1:])],
            "skipped": skip,
            "mu_norms": {"program": compare.leaf_norms(got[0]["mu_norms"]), "reference": compare.leaf_norms(ref[0]["mu_norms"])},
        },
    }


def _reference(cfg: Dict[str, Any], snap: Dict[str, Any], config_file: Dict[str, Any]):
    """The reference's dispatches, worked out once for a set of captured inputs (the stand-ins share them)."""
    if "_reference" not in snap:
        ref_mod = load_module("reference", config_file["reference"])
        snap["_reference"] = follow(ref_mod, snap["inputs"][0], snap["outputs"], hyperparams(cfg), model_config(cfg))
    return snap["_reference"]


def check(cfg: Dict[str, Any], snap: Dict[str, Any], config_file: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """{name: {"value", "limit"}} for the numbers that decide `correct`, and without a limit what says where a gap sits."""
    reference = _reference(cfg, snap, config_file)
    limits = config_file["limits"]
    got = numbers(snap["inputs"][0], snap["outputs"], reference)
    out: Dict[str, Dict[str, Any]] = {k: {"value": got[k], "limit": limits[k]} for k in limits}
    out["where"] = {"value": got["_where"]}
    out["losses"] = {"value": {"program": [list(map(float, g["losses"])) for g in snap["outputs"]],
                               "reference": [list(map(float, r["losses"])) for r in reference]}}
    return out


def stand_in(cfg: Dict[str, Any], snap: Dict[str, Any], config_file: Dict[str, Any], name: str) -> Dict[str, Any]:
    """What the probes would have copied had ``name`` stood in the program's place: ``control``, the reference in the
    precision below the configuration's, or a fault the reference plants (``window``, ``no_shared``, ``half_batch``),
    on the tokens the program sampled."""
    _reference(cfg, snap, config_file)
    ref_mod = load_module("reference", config_file["reference"])
    how = {"precision": config_file["control_precision"]} if name == "control" else {"fault": name}
    other = follow(ref_mod, snap["inputs"][0], snap["outputs"], hyperparams(cfg), model_config(cfg), **how)
    teacher = [{k: g[k] for k in ROLLOUT_KEYS} for g in snap["outputs"]]
    return dict(snap, outputs=[dict(t, **o) for t, o in zip(teacher, other)])
