"""The fused recurrent PPO path with a decoder core whose attention layers read the keys a learned indexer selects:
what the harness must know of it beyond ``programs/ppo_recurrent_anakin.py``.

The program's names, the work of a dispatch, what is captured of its first dispatches and the six numbers compared
are that file's, taken as they stand.  What differs is the past the reference is given (every column's keys, values
and index keys, made under the parameters that made them: ``follow``), the count of operations (``flops_sparse``),
one number more, and the faults the reference can plant.  The number is ``select_gap``: a fault in the selection
drowns in means over the rollout's log-probabilities as a fault in the taps did (``ppo_recurrent_hybrid``), so the
selection itself is compared.  The program's decode steps give out the slots each sparse layer selected (every step
of the rollout: ``stats["selected"]``); for every step of the first dispatch and every env, the set of positions the
program selected is held against the set the reference selects in float32 from the same tokens by the Jaccard
distance ``1 - |A and B| / |A or B|``, averaged over the steps, the worst layer's.  Near-ties of the index scores at
the selection's boundary fall the other way in bf16, so a sound run reads above nought; reading keys the indexer did
not choose (the newest, or all of them) reads far above.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from chipbench import flops, flops_sparse
from chipbench.harness import load_module

base = load_module("programs", "ppo_recurrent_anakin")

STEADY, HOST_PROBES, DEVICE_CALLS, ROLLOUT_KEYS, GROUP = base.STEADY, base.HOST_PROBES, base.DEVICE_CALLS, base.ROLLOUT_KEYS, base.GROUP
before_window, is_steady, work_per_iteration, work_per_call = base.before_window, base.is_steady, base.work_per_iteration, base.work_per_call
observe, capture_inputs, param_shapes = base.observe, base.capture_inputs, base.param_shapes
hyperparams, model_config = base.hyperparams, base.model_config


def flops_per_update(cfg: Dict[str, Any], shapes: flops.Shapes) -> float:
    a, w = cfg["algo"], cfg["env"]["wrapper"]
    return flops_sparse.ppo_sparse(
        shapes, a["decoder"], cfg["env"]["num_envs"] * a["rollout_steps"], a["update_epochs"], base._minibatches(cfg),
        w["len_min"], w["len_max"],
    )


def capture_outputs(out) -> Dict[str, Any]:
    """What ``ppo_recurrent_anakin`` captures, and of the first dispatch the slots its decode steps selected
    ((T, B, sparse layers, topk), -1 where fewer positions were written; 67 MB at the cell's size)."""
    first = base._dispatches[0] == 0
    got = base.capture_outputs(out)
    if first:
        got["selected"] = out[5]["selected"]
    return got


def _positions_of_slots(selected, length: int) -> List[np.ndarray]:
    """The program's selection (T, B, layers, topk) of slots, which are positions in a cache as long as the longest
    episode, as masks over positions: per layer (B, T, length) bool."""
    selected = np.asarray(selected)
    T, B, L, _ = selected.shape
    masks = []
    for layer in range(L):
        mask = np.zeros((B, T, length + 1), bool)
        rows = np.moveaxis(selected[:, :, layer], 0, 1)  # (B, T, topk)
        b, t, _ = np.indices(rows.shape)
        mask[b, t, np.where(rows >= 0, rows, length)] = True  # -1: nothing selected there
        masks.append(mask[..., :length])
    return masks


def _positions_of_columns(sel: np.ndarray, pos_cols: np.ndarray, length: int) -> np.ndarray:
    """The reference's selection (B, T, columns) over the columns whose positions are ``pos_cols`` (B, columns) as a
    mask over positions (B, T, length)."""
    mask = np.zeros(sel.shape[:2] + (length,), bool)
    b, t, c = np.nonzero(sel)
    mask[b, t, pos_cols[b, c]] = True
    return mask


def follow(ref, inputs: Dict[str, Any], rollouts: List[Dict[str, Any]], hp: Dict[str, Any], model: Dict[str, Any],
           precision: str = "f32", fault=None) -> List[Dict[str, Any]]:
    """The reference through the dispatches whose rollouts the program sampled (``rollouts``: tokens, actions,
    rewards, resets, mask of each), from the program's first parameters, Adam state and key, feeding itself.
    Its past holds the keys, values and index keys of every column an env has seen."""
    import jax
    import jax.numpy as jnp

    static_hp, cfg = ref._Static(hp), ref._Static(model)
    code = np.int32(ref.FAULT_CODES.get(fault, 0))  # a traced flag of the one program: these faults cost no compile
    fault = None if fault in ref.FAULT_CODES else fault
    length = int(hp["len_max"])
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
        P0 = -(-length // T) * T  # room for the longest episode so far: one shape whatever the seed drew
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
                group = ref.extend_past(group, run(hist[rows, lo:lo + T], h_pos, h_ep, group)[3], h_pos, h_ep, lo)
            for host, dev in zip(jax.tree.leaves(past), jax.tree.leaves(group)):
                host[rows] = np.asarray(dev)
        pos0, ep0 = jnp.asarray(n), jnp.zeros((B,), jnp.int32)
        out = []
        for d, roll in enumerate(rollouts):
            tokens = np.asarray(roll["tokens"])[..., 0].astype(np.int32).T  # (B, T)
            first = np.asarray(roll["is_first"])[..., 0]
            pos, ep = (np.asarray(z).T for z in ref.positions(jnp.asarray(first), pos0, ep0))
            # the selections only where they are compared: the first dispatch's
            keep = (lambda r: r[:4] + (r[5],)) if d == 0 else (lambda r: r[:4])  # noqa: E731
            forward = base._by_group(lambda *a: keep(run(*a)), B, tokens, pos, ep, past)
            logits, values, _, made = forward[:4]
            actions = np.asarray(roll["actions"])[..., 0].astype(np.int32).T
            logp_all = np.asarray(jax.nn.log_softmax(jnp.asarray(logits), axis=-1))
            logp = np.take_along_axis(logp_all, actions[..., None], axis=-1)[..., 0]
            # the value after the last step: one more token on the past and this rollout
            lo = P0 + d * T
            # columns written in place: `past` keeps the old `ep`, under which the new columns hold nothing
            after = dict(past, pos=past["pos"].copy(), ep=past["ep"].copy())
            for whole_layer, part_layer in zip(after["layers"], made):
                for whole, part in zip(whole_layer, part_layer):
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
            record: Dict[str, Any] = {"logprobs": logp.T, "values": values.T}
            if d == 0:  # the positions each layer selected for every step: the past's columns, then the rollout's own
                pos_cols = np.concatenate([past["pos"], pos], axis=1)
                record["select_pos"] = [_positions_of_columns(s, pos_cols, length) for s in forward[4]]
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


def select_gaps(got: Dict[str, Any], ref: Dict[str, Any], length: int) -> List[float]:
    """Per sparse layer, the Jaccard distance of the positions selected at each step of the first dispatch (every
    env), program's (or a stand-in's) against the reference's, averaged over the steps."""
    mine = got["select_pos"] if "select_pos" in got else _positions_of_slots(got["selected"], length)
    gaps = []
    for a, b in zip(mine, ref["select_pos"]):
        both, either = np.sum(a & b, axis=-1), np.sum(a | b, axis=-1)
        gaps.append(float(np.mean(1.0 - both / np.maximum(either, 1))))
    return gaps


def numbers(inputs: Dict[str, Any], got: List[Dict[str, Any]], ref: List[Dict[str, Any]], length: int) -> Dict[str, Any]:
    """The six numbers of ``ppo_recurrent_anakin.numbers`` and ``select_gap``, the worst layer's of ``select_gaps``;
    ``check`` judges those the configuration's file gives a limit."""
    out = base.numbers(inputs, got, ref)
    by_layer = select_gaps(got[0], ref[0], length)
    out["_where"].update(select_gaps=by_layer)
    return {**out, "select_gap": max(by_layer)}


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
    got = numbers(snap["inputs"][0], snap["outputs"], reference, hyperparams(cfg)["len_max"])
    out: Dict[str, Dict[str, Any]] = {k: {"value": got[k], "limit": limits[k]} for k in limits}
    out.update({k: {"value": v} for k, v in got.items() if k not in limits and not k.startswith("_")})  # read, not judged
    out["where"] = {"value": got["_where"]}
    out["losses"] = {"value": {"program": [list(map(float, g["losses"])) for g in snap["outputs"]],
                               "reference": [list(map(float, r["losses"])) for r in reference]}}
    return out


def stand_in(cfg: Dict[str, Any], snap: Dict[str, Any], config_file: Dict[str, Any], name: str) -> Dict[str, Any]:
    """What the probes would have copied had ``name`` stood in the program's place: ``control``, the reference in the
    precision below the configuration's, or a fault the reference plants (``recent_keys``, ``dense_keys``,
    ``no_index_loss``, ``half_batch``), on the tokens the program sampled."""
    _reference(cfg, snap, config_file)
    ref_mod = load_module("reference", config_file["reference"])
    how = {"precision": config_file["control_precision"]} if name == "control" else {"fault": name}
    other = follow(ref_mod, snap["inputs"][0], snap["outputs"], hyperparams(cfg), model_config(cfg), **how)
    teacher = [{k: g[k] for k in ROLLOUT_KEYS} for g in snap["outputs"]]
    return dict(snap, outputs=[dict(t, **o) for t, o in zip(teacher, other)])
