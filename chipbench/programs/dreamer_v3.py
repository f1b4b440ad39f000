"""The coupled DreamerV3 path: host player, env step through the adapter, device ring, fused sample+update.

Knows the program's names (executables, argument order, the ring's ``add``), nothing of its
code.  The steady executable is ``dreamer_v3.train_phase_device`` at the window length the
replay ratio owes every iteration: ``(params, opt_state, [health,] buffers, cursor, key, counter,
n_samples=U)`` -> ``(params, opt_state, [health,] counter, metrics)``.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from chipbench import compare, flops
from chipbench.harness import load_module

STEADY = "dreamer_v3.train_phase_device"
PLAYER = "dreamer_v3.player_step"
HOST_PROBES: Dict[str, str] = {
    "sheeprl_tpu.data.device_replay.DeviceReplay.add": "replay.add",
    "sheeprl_tpu.parallel.fabric.PlayerSync.before_dispatch": "player.sync",
    "sheeprl_tpu.envs.jax.adapter.JaxToGymAdapter.step": "env.step",
}
DEVICE_CALLS = frozenset({"replay.add", "player.sync"})  # which of the host probes dispatch device work
RING_KEYS = ("rgb", "actions", "rewards", "terminated", "is_first")


def device_result(label: str, args, out) -> Any:
    """What a traced run waits on after a host probe that dispatched device work."""
    return args[0].buffers if label == "replay.add" else out


def before_window(snap: Dict[str, Any]) -> None:
    """Warm the ring write for every count of envs that can finish in one step.

    ``add(rows, indices=done)`` is a program of its own for each ``len(done)``.  The envs start
    together and are cut at the same step, so set-up meets only the all-envs shape; one env that
    eats its last food early (about 2% of episodes) would compile the others inside the window.
    Each shape is driven once here through the ring's public ``gather_at``/``write_at``, writing
    back the rows that are there."""
    ring = snap.pop("replay", None)
    if ring is None or not hasattr(ring, "write_at"):
        return
    n_envs = int(np.asarray(ring.cursor["filled"]).shape[0])
    for k in range(1, n_envs):
        envs = list(range(k))
        slot = np.zeros((1, k), np.int32)
        for key in ring.buffers:
            rows = np.asarray(ring.gather_at(key, slot, np.asarray(envs, np.int32)))
            ring.write_at(key, rows, slot, envs)


def _steady_updates(cfg: Dict[str, Any]) -> int:
    return int(cfg["env"]["num_envs"] * cfg["env"]["action_repeat"] * cfg["algo"]["replay_ratio"])


def is_steady(cfg: Dict[str, Any], args, kwargs) -> bool:
    return int(kwargs.get("n_samples", 0)) == _steady_updates(cfg)


def work_per_iteration(cfg: Dict[str, Any]) -> Dict[str, int]:
    return {"env_steps": int(cfg["env"]["num_envs"] * cfg["env"]["action_repeat"])}


def work_per_call(cfg: Dict[str, Any], name: str, args, kwargs) -> Dict[str, int]:
    return {"updates": int(kwargs["n_samples"])} if name == STEADY else {}


def flops_per_update(cfg: Dict[str, Any], shapes: flops.Shapes) -> float:
    a = cfg["algo"]
    return flops.dv3(shapes, int(a["per_rank_batch_size"]), int(a["per_rank_sequence_length"]), int(a["horizon"]))


# ----------------------------------------------------------------------------
# what `correct` captures
# ----------------------------------------------------------------------------

def _split(args):
    """(params, opt_state, cursor, key) whether or not the health state rides along."""
    if len(args) == 7:
        params, opt_state, _health, _buffers, cursor, key, _counter = args
    else:
        params, opt_state, _buffers, cursor, key, _counter = args
    return params, opt_state, cursor, key


def capture_inputs(args, kwargs, step: int) -> Dict[str, Any]:
    params, opt_state, cursor, key = _split(args)
    light = {"filled": cursor["filled"], "pos": cursor["pos"], "key": key, "n_samples": int(kwargs["n_samples"])}
    if step > 0:
        return light
    return dict(light, params=params, adam={k: compare.adam_state(v) for k, v in opt_state.items()})


def capture_outputs(out) -> Dict[str, Any]:
    params, opt_state, metrics = out[0], out[1], out[-1]
    return {"params": params, "adam": {k: compare.adam_state(v) for k, v in opt_state.items()}, "losses": tuple(metrics)}


def observe(label: str, args, kwargs, out, snap: Dict[str, Any]) -> None:
    """Keeps, until warm-up ends, what the ring was given (in the order it was stored) and one player step."""
    if label == "replay.add":
        snap.setdefault("replay", args[0])
        data = args[1] if len(args) > 1 else kwargs["data"]
        indices = args[2] if len(args) > 2 else kwargs.get("indices")
        first = np.asarray(next(iter(data.values())))
        envs = list(range(first.shape[1])) if indices is None else [int(i) for i in indices]
        ring = snap.setdefault("ring", {})
        for col, env in enumerate(envs):
            rows = ring.setdefault(env, {k: [] for k in RING_KEYS})
            for k in RING_KEYS:
                rows[k].extend(np.array(np.asarray(data[k])[:, col]))
    elif label == PLAYER and out is not None:
        import jax

        # the last player step before the window opens: its carry has left the all-zero start
        params, carry, obs, key = args[:4]
        new_carry, action, _ = out
        snap["player"] = jax.device_get({
            "params": params, "carry": carry, "rgb": obs["rgb"], "key": key,
            "h": new_carry[0], "z": new_carry[1], "action": action,
        })


def param_shapes(inputs: Dict[str, Any]) -> flops.Shapes:
    return flops.shapes_of({k: v for k, v in inputs["params"].items() if k != "moments"})


# ----------------------------------------------------------------------------
# correct
# ----------------------------------------------------------------------------

def hyperparams(cfg: Dict[str, Any]) -> Dict[str, Any]:
    a = cfg["algo"]
    wm = a["world_model"]
    if wm["decoupled_rssm"] or not wm["learnable_initial_recurrent_state"]:
        raise ValueError("the reference has the coupled RSSM with a learnable initial state only")
    if int(a["critic"]["per_rank_target_network_update_freq"]) != 1:
        raise ValueError("the reference moves the target critic after every update")

    def opt(group, clip):
        o = group["optimizer"]
        betas = o.get("betas", [0.9, 0.999])
        return {"lr": float(o["lr"]), "eps": float(o["eps"]), "b1": float(betas[0]), "b2": float(betas[1]), "clip": float(clip)}

    return {
        "batch": int(a["per_rank_batch_size"]), "seq_len": int(a["per_rank_sequence_length"]),
        "horizon": int(a["horizon"]), "gamma": float(a["gamma"]), "lmbda": float(a["lmbda"]),
        "mlp_layers": int(a["mlp_layers"]), "unimix": float(a["unimix"]), "actor_unimix": float(a["actor"]["unimix"]),
        "stoch": int(wm["stochastic_size"]), "discrete": int(wm["discrete_size"]),
        "recurrent": int(wm["recurrent_model"]["recurrent_state_size"]),
        "kl_dynamic": float(wm["kl_dynamic"]), "kl_representation": float(wm["kl_representation"]),
        "free_nats": float(wm["kl_free_nats"]), "kl_regularizer": float(wm["kl_regularizer"]),
        "continue_scale": float(wm["continue_scale_factor"]), "ent_coef": float(a["actor"]["ent_coef"]),
        "tau": float(a["critic"]["tau"]),
        "moments": {"decay": float(a["actor"]["moments"]["decay"]), "max": float(a["actor"]["moments"]["max"]),
                    "low": float(a["actor"]["moments"]["percentile"]["low"]), "high": float(a["actor"]["moments"]["percentile"]["high"])},
        "opt": {"world_model": opt(wm, wm["clip_gradients"]), "actor": opt(a["actor"], a["actor"]["clip_gradients"]),
                "critic": opt(a["critic"], a["critic"]["clip_gradients"])},
    }


def ring_arrays(ring: Dict[int, Dict[str, List[np.ndarray]]]) -> Dict[str, np.ndarray]:
    """(rows, envs, ...) arrays of what the ring was given, each env in stored order, padded with zeros."""
    envs = sorted(ring)
    rows = max(len(ring[e]["rewards"]) for e in envs)
    out = {}
    for k in RING_KEYS:
        sample = np.asarray(ring[envs[0]][k][0])
        arr = np.zeros((rows, len(envs)) + sample.shape, sample.dtype)
        for col, e in enumerate(envs):
            stacked = np.stack(ring[e][k])
            arr[: len(stacked), col] = stacked
        out[k] = arr
    return out


def follow(ref, snap: Dict[str, Any], hp: Dict[str, Any], precision: str = "f32", fault=None) -> List[Dict[str, Any]]:
    """The reference from the program's first inputs through the captured dispatches, feeding itself."""
    import jax
    import jax.numpy as jnp

    first = snap["inputs"][0]
    p = jax.tree.map(jnp.asarray, first["params"])
    opt = jax.tree.map(jnp.asarray, first["adam"])
    ring = {k: jnp.asarray(v) for k, v in ring_arrays(snap["ring"]).items()}
    static = ref.freeze(hp)
    out = []
    with jax.default_matmul_precision("highest"):
        for step in snap["inputs"]:
            if np.any(np.asarray(step["filled"]) > ring["rewards"].shape[0]):
                raise ValueError("the ring was given fewer rows than its cursor counts")
            p, opt, metrics = ref.dispatch(
                p, opt, ring, jnp.asarray(step["filled"]), jnp.asarray(step["key"]),
                hp_static=static, n_samples=step["n_samples"], precision=precision, fault=fault,
            )
            out.append(jax.device_get({"params": p, "adam": opt, "losses": tuple(metrics)}))
    return out


# world model, observation, reward, state (kl loss), continue, kl, policy, value, posterior and prior entropy
LOSS_FLOORS = (1.0, 1.0, 1e-2, 1e-1, 1e-3, 1e-1, 1e-2, 1e-1, 1.0, 1.0)
GROUPS = ("world_model", "actor", "critic")


def numbers(first: Dict[str, Any], got: List[Dict[str, Any]], ref: List[Dict[str, Any]]) -> Dict[str, Any]:
    loss_gaps = [
        [compare.scalar_gap(p, r, floor) for p, r, floor in zip(g["losses"], f["losses"], LOSS_FLOORS)]
        for g, f in zip(got, ref)
    ]
    grouped = lambda step, what: {k: step["adam"][k][what] for k in GROUPS}  # noqa: E731
    mu = compare.leaf_gaps(compare.leaf_norms(grouped(got[0], "mu")), compare.leaf_norms(grouped(ref[0], "mu")))
    # leaves whose gradient is nought to rounding in the reference move by round-off alone
    skip = compare.tiny_gradient_leaves(compare.leaf_norms(grouped(ref[0], "nu")), share=1e-6)  # nu is the gradient squared
    trained = lambda tree: {k: tree[k] for k in GROUPS}  # noqa: E731
    dp = compare.leaf_gaps(
        compare.change_norms(trained(got[-1]["params"]), trained(first["params"])),
        compare.change_norms(trained(ref[-1]["params"]), trained(first["params"])), skip=skip,
    )
    nu = compare.leaf_gaps(compare.leaf_norms(grouped(got[0], "nu")), compare.leaf_norms(grouped(ref[0], "nu")))
    # the world model's total loss, apart: a mean over B*L tokens that rounding moves by 1e-4 and half a batch by 4e-3
    return {"model_loss_gap": max(row[0] for row in loss_gaps), "loss_gap": max(max(row) for row in loss_gaps),
            "moment_gap": max(mu.values()), "change_gap": max(dp.values()),
            "_where": {"loss_gaps": loss_gaps, "second_moment_gap": compare.worst_few(nu, 3), "moment_gap": compare.worst_few(mu), "change_gap": compare.worst_few(dp),
                       "moment_median": float(np.median(list(mu.values()))), "change_median": float(np.median(list(dp.values()))),
                       "skipped": len(skip)}}


def player_reference(ref, snap: Dict[str, Any], hp: Dict[str, Any], precision: str = "f32"):
    """The reference's player step on the captured inputs: its recurrent state and its action logits under
    the Gumbel noise of the captured key (``categorical(key, logits)`` is ``argmax(logits + gumbel(key))``)."""
    import jax
    import jax.numpy as jnp

    pl = snap["player"]
    with jax.default_matmul_precision("highest"):
        (h, _z, _a), perturbed, _ = ref.player_step(
            jax.tree.map(jnp.asarray, pl["params"]), tuple(jnp.asarray(c, jnp.float32) for c in pl["carry"]),
            jnp.asarray(pl["rgb"], jnp.float32), jnp.asarray(pl["key"]), hp_static=ref.freeze(hp), precision=precision,
        )
    return np.asarray(h, np.float64), np.asarray(perturbed, np.float64)


def player_numbers(got_h, took, h, perturbed) -> Dict[str, float]:
    """One env-interaction step of the player: how far its recurrent state lies from the reference's, and by
    how much the action it took lies below the reference's best under the same noise (0 where they agree)."""
    below = perturbed.max(-1) - np.take_along_axis(perturbed, took[..., None], -1)[..., 0]
    return {"player_state_gap": float(np.linalg.norm(got_h - h) / np.linalg.norm(h)),
            "player_action_gap": float(below.max())}


def _reference(cfg: Dict[str, Any], snap: Dict[str, Any], config_file: Dict[str, Any]):
    """The reference's dispatches and its player step, worked out once for what the probes copied."""
    if "_reference" not in snap:
        ref_mod = load_module("reference", config_file["reference"])
        hp = hyperparams(cfg)
        snap["_reference"] = (follow(ref_mod, snap, hp), player_reference(ref_mod, snap, hp))
    return snap["_reference"]


def check(cfg: Dict[str, Any], snap: Dict[str, Any], config_file: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """{name: {"value", "limit"}} for the numbers that decide `correct`, and without a limit what says where a gap sits."""
    reference, (ref_h, ref_perturbed) = _reference(cfg, snap, config_file)
    limits = config_file["limits"]
    got = numbers(snap["inputs"][0], snap["outputs"], reference)
    pl = snap["player"]
    got.update(player_numbers(np.asarray(pl["h"], np.float64), np.asarray(pl["action"]).argmax(-1), ref_h, ref_perturbed))
    out: Dict[str, Dict[str, Any]] = {k: {"value": got[k], "limit": limits[k]} for k in limits}
    out["where"] = {"value": got["_where"]}
    out["more"] = {"value": {k: v for k, v in got.items() if k not in limits and not k.startswith("_")}}
    out["losses"] = {"value": {"program": [list(map(float, g["losses"])) for g in snap["outputs"]],
                               "reference": [list(map(float, r["losses"])) for r in reference]}}
    return out


def stand_in(cfg: Dict[str, Any], snap: Dict[str, Any], config_file: Dict[str, Any], name: str) -> Dict[str, Any]:
    """What the probes would have copied had ``name`` stood in the program's place: ``control``, the reference in the
    precision below the configuration's (train dispatches and player step), or a fault the reference plants
    (``half_batch``; the player step has no batch to halve and stays the program's)."""
    _reference(cfg, snap, config_file)
    ref_mod = load_module("reference", config_file["reference"])
    hp = hyperparams(cfg)
    if name != "control":
        return dict(snap, outputs=follow(ref_mod, snap, hp, fault=name))
    low = config_file["control_precision"]
    low_h, low_perturbed = player_reference(ref_mod, snap, hp, precision=low)
    return dict(snap, outputs=follow(ref_mod, snap, hp, precision=low), player=dict(snap["player"], h=low_h, action=low_perturbed))
