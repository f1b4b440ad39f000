"""The fused recurrent PPO path with a decoder core that has state-space layers (Mamba-2 mixers beside attention,
layers of one part): what the harness must know of it beyond ``programs/ppo_recurrent_anakin.py``.

The program's names, the work of a dispatch, what is captured of its first dispatches and the numbers compared
are that file's, taken as they stand.  What differs is the recurrent state the reference has to be given as its
past: every column's keys and values for an attention layer, but for a Mamba-2 layer a state, which has no
columns: the state and the last ``K - 1`` convolution inputs at the rollout's start, made under the parameters
that made them (``follow``); the count of operations (``flops_ssm``); two more numbers, because a fault in the
state drowns in means over 8,192 steps as a fault in the taps did (``ppo_recurrent_hybrid``): ``state_gap``, the
carry's states after the first dispatch (prefilled through the chunked scan, then stepped 256 times) against
the reference's recurrence, and two medians over a few steps of the first dispatch: ``carry_gap`` over its first
steps, which read the carry's state before a dispatch's own tokens have replaced it, and ``reset_gap`` over the steps
just after an episode's start; and the faults the reference can plant.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from chipbench import flops, flops_ssm
from chipbench.harness import load_module

base = load_module("programs", "ppo_recurrent_anakin")
hybrid = load_module("programs", "ppo_recurrent_hybrid")

STEADY, HOST_PROBES, DEVICE_CALLS, ROLLOUT_KEYS, GROUP = base.STEADY, base.HOST_PROBES, base.DEVICE_CALLS, base.ROLLOUT_KEYS, base.GROUP
before_window, is_steady, work_per_iteration, work_per_call = base.before_window, base.is_steady, base.work_per_iteration, base.work_per_call
observe, capture_inputs, param_shapes = base.observe, base.capture_inputs, base.param_shapes
hyperparams, model_config = base.hyperparams, base.model_config
MAMBA = "mamba2"
REACH = 8  # steps from the dispatch's start that `carry_gap` reads, and positions from an episode's start that `reset_gap` reads


def flops_per_update(cfg: Dict[str, Any], shapes: flops.Shapes) -> float:
    a, w = cfg["algo"], cfg["env"]["wrapper"]
    return flops_ssm.ppo_ssm(
        shapes, a["decoder"], cfg["env"]["num_envs"] * a["rollout_steps"], a["update_epochs"], base._minibatches(cfg),
        w["len_min"], w["len_max"],
    )


def capture_outputs(out) -> Dict[str, Any]:
    """What ``ppo_recurrent_anakin`` captures, and after the first dispatch the carry's state-space states
    (268 MB at the cell's size; nothing on a program whose carry has none)."""
    first = base._dispatches[0] == 0
    got = base.capture_outputs(out)
    if first:
        got["ssm_states"] = list(out[2]["carry"].get("ssm", []))
    return got


def follow(ref, inputs: Dict[str, Any], rollouts: List[Dict[str, Any]], hp: Dict[str, Any], model: Dict[str, Any],
           precision: str = "f32", fault=None) -> List[Dict[str, Any]]:
    """The reference through the dispatches whose rollouts the program sampled (``rollouts``: tokens, actions,
    rewards, resets, mask of each), from the program's first parameters, Adam state and key, feeding itself.
    Its past holds, for an attention layer, the keys and values of every column an env has seen, and for a
    Mamba-2 layer the state and the last convolution inputs where the env stands."""
    import jax
    import jax.numpy as jnp

    static_hp, cfg, kinds = ref._Static(hp), ref._Static(model), tuple(model["layer_types"])
    code = np.int32(ref.FAULT_CODES.get(fault, 0))  # a traced flag of the one program: the state faults cost no compile
    fault = None if fault in ref.FAULT_CODES else fault
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(jnp.asarray, inputs["params"])
        adam = inputs["adam"]
        if float(adam["mu_max"]) != 0.0 or float(adam["nu_max"]) != 0.0 or int(adam["count"]) != 0:
            raise ValueError("the reference follows a run from its first update: Adam's state was not nought")
        mu, nu, count = jax.tree.map(jnp.zeros_like, params), jax.tree.map(jnp.zeros_like, params), jnp.asarray(adam["count"])
        key = jnp.asarray(inputs["key"])
        env = inputs["env"]
        n = np.asarray(env["t"], np.int32)  # steps each env's episode has behind it: the history the carry was filled from
        if not np.array_equal(n, np.asarray(inputs["pos"])):
            raise ValueError("the carry's positions are not the envs' steps: the carry does not hold the episodes so far")
        B, T = n.shape[0], np.asarray(rollouts[0]["tokens"]).shape[0]
        P0 = -(-int(hp["len_max"]) // T) * T  # room for the longest episode so far: one shape whatever the seed drew
        P = P0 + len(rollouts) * T
        past = jax.tree.map(lambda z: np.zeros(z.shape, z.dtype), jax.eval_shape(lambda: ref.empty_past(model, B, P)))  # on the host
        past["ep"] -= 1  # -1: nothing there
        # the episodes so far under the first parameters (what the program's prefill left in the carry), a rollout's length at a time
        hist = np.asarray(ref.history(jnp.asarray(env["prompt"]), jnp.asarray(env["prompt_len"]), jnp.asarray(n), P0))
        run = lambda tok, p_, e_, pa: ref.forward_jit(params, tok, p_, e_, pa, code, cfg=cfg, precision=precision, fault=fault)  # noqa: E731
        for g in range(0, B, GROUP):
            rows = slice(g, g + GROUP)
            group = jax.tree.map(lambda z: jnp.asarray(z[rows]), past)
            for lo in range(0, int(n[rows].max()), T):
                h_pos = np.broadcast_to(np.arange(lo, lo + T, dtype=np.int32), (n[rows].shape[0], T))
                h_ep = np.where(h_pos < n[rows, None], 0, -1).astype(np.int32)
                group = ref.extend_past(group, run(hist[rows, lo:lo + T], h_pos, h_ep, group)[3], h_pos, h_ep, lo, kinds=kinds)
            for host, dev in zip(jax.tree.leaves(past), jax.tree.leaves(group)):
                host[rows] = np.asarray(dev)
        pos0, ep0 = jnp.asarray(n), jnp.zeros((B,), jnp.int32)
        out = []
        for d, roll in enumerate(rollouts):
            tokens = np.asarray(roll["tokens"])[..., 0].astype(np.int32).T  # (B, T)
            first = np.asarray(roll["is_first"])[..., 0]
            pos, ep = (np.asarray(z).T for z in ref.positions(jnp.asarray(first), pos0, ep0))
            logits, values, _, made = base._by_group(run, B, tokens, pos, ep, past)
            actions = np.asarray(roll["actions"])[..., 0].astype(np.int32).T
            logp_all = np.asarray(jax.nn.log_softmax(jnp.asarray(logits), axis=-1))
            logp = np.take_along_axis(logp_all, actions[..., None], axis=-1)[..., 0]
            # the value after the last step: one more token on the past and this rollout
            lo = P0 + d * T
            # columns written in place: `past` keeps the old `ep`, under which the new columns hold nothing; a state replaced
            after = dict(past, pos=past["pos"].copy(), ep=past["ep"].copy(), layers=list(past["layers"]))
            for i, kind in enumerate(kinds):
                if kind == MAMBA:
                    after["layers"][i] = tuple(made[i])
                else:
                    for whole, part in zip(after["layers"][i], made[i]):
                        whole[:, lo:lo + T] = part
            after["pos"][:, lo:lo + T], after["ep"][:, lo:lo + T] = pos, ep
            n_first = np.asarray(roll["next_is_first"])
            n_pos, n_ep = (np.asarray(z).T for z in ref.positions(
                jnp.asarray(n_first.reshape(1, B)), jnp.asarray(pos[:, -1] + 1), jnp.asarray(ep[:, -1])))
            # ... through the rollout's own shape (the one program compiled): the token first, padding after it
            pad = lambda z, fill: np.concatenate([z, np.full((B, T - 1), fill, np.int32)], axis=1)  # noqa: E731
            n_tok = pad(np.asarray(roll["next_tokens"]).reshape(B, 1).astype(np.int32), 0)
            last_v = base._by_group(lambda *a: run(*a)[1], B, n_tok, pad(n_pos, 0), pad(n_ep, -1), after)[:, 0]
            returns, adv = ref.gae(
                jnp.asarray(roll["rewards"]), jnp.asarray(values.T), jnp.asarray(roll["dones"]), jnp.asarray(last_v),
                hp["gamma"], hp["gae_lambda"])
            mask = np.asarray(roll["mask"]).T
            whole = {"tokens": tokens, "pos": pos, "ep": ep, "actions": actions, "old_logp": logp,
                     "advantages": np.asarray(adv).T, "returns": np.asarray(returns).T, "mask": mask}
            _k_roll, k_train, key = jax.random.split(key, 3)
            record: Dict[str, Any] = {"logprobs": logp.T, "values": values.T, "pos": pos.T}
            if d == 0:  # the states the first dispatch leaves: the prefill's, then 256 steps of the recurrence
                record["ssm_states"] = [made[i][0] for i, kind in enumerate(kinds) if kind == MAMBA]
            load = 0
            for e, k_e in enumerate(jax.random.split(k_train, hp["update_epochs"])):
                perm = np.asarray(jax.random.permutation(k_e, B))
                for i in range(hp["num_minibatches"]):
                    idx = perm[i * hp["env_bs"]:(i + 1) * hp["env_bs"]]
                    take = lambda z: z[idx]  # noqa: E731
                    params, mu, nu, count, losses, counts = ref.update(
                        params, mu, nu, count, jax.tree.map(take, whole), jax.tree.map(take, past), code,
                        cfg=cfg, hp=static_hp, precision=precision, fault=fault)
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


def state_gaps(got: List[Dict[str, Any]], ref: List[Dict[str, Any]]) -> List[float]:
    """The state-space states after the first dispatch, a layer each: the norm of the difference over the reference's
    norm (none for a model without such a layer)."""
    gaps = []
    for g, r in zip(got[0].get("ssm_states", []), ref[0].get("ssm_states", [])):
        g, r = np.asarray(g, np.float64), np.asarray(r, np.float64)
        gap = float(np.linalg.norm(g - r) / max(float(np.linalg.norm(r)), 1e-30))
        gaps.append(gap if np.isfinite(gap) else float("inf"))
    return gaps


def numbers(inputs: Dict[str, Any], got: List[Dict[str, Any]], ref: List[Dict[str, Any]]) -> Dict[str, Any]:
    """The six numbers of ``ppo_recurrent_anakin.numbers``; ``state_gap``, the worst layer's of ``state_gaps``; and the
    two medians of ``ppo_recurrent_hybrid.tap_gaps`` (the median absolute gap of the log-probabilities and of the
    values, on the reference's standard deviation, the larger of the two) over steps of the FIRST dispatch:
    ``carry_gap`` over its first ``REACH`` steps (32 x 8 at the cell's size: a state decays within tens of steps, so
    only these still read what the carry held), ``reset_gap`` over its steps at a position under ``REACH`` (nought
    where no episode started in it).  The first dispatch alone, because this model's program and reference part ways
    after it: under the stock step their later log-probabilities differ by 0.1 to 0.3 and their values by 0.2 to 40
    standard deviations at every step, a start or not.  ``check`` judges those the configuration's file gives a limit."""
    out = base.numbers(inputs, got, ref)
    taps = hybrid.tap_gaps(got[:1], ref[:1], REACH)
    by_layer = state_gaps(got, ref)
    out["_where"].update(reset_steps=taps["_reset_steps"], state_gaps=by_layer)
    return {**out, "state_gap": max(by_layer, default=0.0), "carry_gap": taps["carry_tap_gap"], "reset_gap": taps["reset_tap_gap"]}


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
    out.update({k: {"value": v} for k, v in got.items() if k not in limits and not k.startswith("_")})  # read, not judged
    out["where"] = {"value": got["_where"]}
    out["losses"] = {"value": {"program": [list(map(float, g["losses"])) for g in snap["outputs"]],
                               "reference": [list(map(float, r["losses"])) for r in reference]}}
    return out


def stand_in(cfg: Dict[str, Any], snap: Dict[str, Any], config_file: Dict[str, Any], name: str) -> Dict[str, Any]:
    """What the probes would have copied had ``name`` stood in the program's place: ``control``, the reference in the
    precision below the configuration's, or a fault the reference plants (``ssm_reset``, ``ssm_prefix``,
    ``one_bc_group``, ``no_shared``, ``half_batch``), on the tokens the program sampled."""
    _reference(cfg, snap, config_file)
    ref_mod = load_module("reference", config_file["reference"])
    how = {"precision": config_file["control_precision"]} if name == "control" else {"fault": name}
    other = follow(ref_mod, snap["inputs"][0], snap["outputs"], hyperparams(cfg), model_config(cfg), **how)
    teacher = [{k: g[k] for k in ROLLOUT_KEYS} for g in snap["outputs"]]
    return dict(snap, outputs=[dict(t, **o) for t, o in zip(teacher, other)])
