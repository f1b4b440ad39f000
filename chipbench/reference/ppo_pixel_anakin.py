"""Plain float32 reference of one fused Anakin PPO dispatch on the multi-room pixel env.

One dispatch = a 128-step rollout of ``num_envs`` procedurally generated multi-room
grid worlds under the current policy (truncation bootstrap included), GAE, then
``update_epochs`` x ``num_minibatches`` clipped-PPO Adam updates.  Everything is
``jax.numpy`` in float32 with matmuls at ``highest`` precision, no kernels, nothing
imported from the program.  Departures from Schulman et al. 2017 follow the program
(the system under test) and are marked ``# program:``.

``precision`` selects how every matmul/convolution reads its operands:
``"f32"`` (the reference), ``"bf16"`` and ``"fp8"`` (per-tensor scaled e4m3; the
control: the nearest precision below the configuration's bf16-mixed).
``fault="half_batch"`` leaves the second half of every minibatch out and takes the
means over the rest.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST

# ----------------------------------------------------------------------------
# operand precision
# ----------------------------------------------------------------------------

def _quantizer(precision: str):
    if precision == "f32":
        return lambda x: x
    if precision == "bf16":
        return lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        def q(x):
            scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0  # e4m3's largest finite value
            return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
        return q
    raise ValueError(f"unknown precision {precision!r}")


# ----------------------------------------------------------------------------
# the agent: three stride-2 4x4 convolutions, a 512 projection, one-layer actor and critic
# ----------------------------------------------------------------------------

def agent_forward(params: Dict[str, Any], rgb: jax.Array, q) -> Tuple[jax.Array, jax.Array]:
    """``rgb`` float32 in [0, 1], (N, 64, 64, 3) -> (logits (N, A), value (N,))."""
    p = params["params"]
    enc = p["feature_extractor"]
    x = rgb
    # program: the pixel trunk is the repo's MultiEncoder CNN (4x4 stride-2 SAME convolutions,
    # 32/64/64 channels), not the 8-4-3 kernels of the Nature DQN paper
    for i in range(3):
        layer = enc["cnn_encoder"][f"conv_{i}"]
        x = lax.conv_general_dilated(
            q(x), q(layer["kernel"]), (2, 2), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI,
        ) + layer["bias"]
        x = jax.nn.relu(x)
    x = x.reshape(x.shape[0], -1)
    dense = lambda layer, y: jnp.dot(q(y), q(layer["kernel"]), precision=HI) + layer["bias"]  # noqa: E731
    feat = jax.nn.relu(dense(enc["cnn_proj"], x))
    logits = dense(p["actor"]["head"], jax.nn.relu(dense(p["actor"]["dense_0"], feat)))
    value = dense(p["critic"]["head"], jax.nn.relu(dense(p["critic"]["dense_0"], feat)))
    return logits, value[..., 0]


# ----------------------------------------------------------------------------
# the environment: procedural multi-room grid world (8x8 cells, 64x64x3 pixels)
# ----------------------------------------------------------------------------

GRID, CELL, N_FOOD, MAX_STEPS = 8, 8, 4, 256
WALL_COLS = (2, 4, 6)
MOVES = np.array([[0, 0], [-1, 0], [1, 0], [0, -1], [0, 1]], np.int32)
RGB = {
    "wall": (128, 128, 128), "door": (200, 0, 0), "open": (60, 60, 60), "key": (255, 255, 0),
    "food": (0, 255, 0), "goal": (0, 0, 255), "agent": (255, 255, 255),
}


class Room(NamedTuple):
    pos: jax.Array
    door_row: jax.Array
    door_open: jax.Array
    key_taken: jax.Array
    key_pos: jax.Array
    food: jax.Array
    goal: jax.Array
    t: jax.Array
    key: jax.Array
    level: jax.Array


def _off_wall(cols):
    on_wall = (cols == WALL_COLS[0]) | (cols == WALL_COLS[1]) | (cols == WALL_COLS[2])
    return jnp.where(on_wall, cols - 1, cols)


def _n_walls(level):
    return 1 + jnp.clip(jnp.floor(level).astype(jnp.int32), 0, 2)


def env_reset(key, level: float) -> Room:
    ks = jax.random.split(key, 8)
    door_row = jax.random.randint(ks[0], (3,), 0, GRID)
    start_row = jax.random.randint(ks[1], (), 0, GRID)
    goal_row = jax.random.randint(ks[2], (), 0, GRID)
    key_row = jax.random.randint(ks[3], (3,), 0, GRID)
    key_col = _off_wall(jax.random.randint(ks[4], (3,), 0, jnp.asarray(WALL_COLS)))
    food_row = jax.random.randint(ks[5], (N_FOOD,), 0, GRID)
    food_col = _off_wall(jax.random.randint(ks[6], (N_FOOD,), 0, GRID))
    return Room(
        pos=jnp.stack([start_row, jnp.zeros((), jnp.int32)]).astype(jnp.int32),
        door_row=door_row.astype(jnp.int32),
        door_open=jnp.zeros((3,), bool),
        key_taken=jnp.zeros((3,), bool),
        key_pos=jnp.stack([key_row, key_col], 1).astype(jnp.int32),
        food=jnp.zeros((GRID, GRID), bool).at[food_row, food_col].set(True),
        goal=jnp.stack([goal_row, jnp.full((), GRID - 1)]).astype(jnp.int32),
        t=jnp.zeros((), jnp.int32),
        key=ks[7],
        level=jnp.full((), level, jnp.float32),
    )


def env_render(s: Room) -> jax.Array:
    """The 64x64x3 uint8 picture of a state: walls, doors, food, keys, goal, agent on top."""
    c = lambda name: jnp.asarray(RGB[name], jnp.uint8)  # noqa: E731
    n_walls = _n_walls(s.level)
    rows, cols = jnp.arange(GRID)[:, None], jnp.arange(GRID)[None, :]
    img = jnp.zeros((GRID, GRID, 3), jnp.uint8)
    for w, col in enumerate(WALL_COLS):
        is_door = (rows == s.door_row[w]) & (cols == col)
        is_wall = (cols == col) & ~is_door
        active = w < n_walls
        img = jnp.where((active & is_wall)[..., None], c("wall"), img)
        img = jnp.where((active & is_door)[..., None], jnp.where(s.door_open[w], c("open"), c("door")), img)
    img = jnp.where(s.food[..., None], c("food"), img)
    for w in range(3):
        at = (rows == s.key_pos[w, 0]) & (cols == s.key_pos[w, 1]) & (w < n_walls) & ~s.key_taken[w]
        img = jnp.where(at[..., None], c("key"), img)
    img = jnp.where(((rows == s.goal[0]) & (cols == s.goal[1]))[..., None], c("goal"), img)
    img = jnp.where(((rows == s.pos[0]) & (cols == s.pos[1]))[..., None], c("agent"), img)
    return jnp.repeat(jnp.repeat(img, CELL, 0), CELL, 1)


def env_step(s: Room, action) -> Tuple[Room, jax.Array, jax.Array, jax.Array]:
    n_walls = _n_walls(s.level)
    cand = jnp.clip(s.pos + jnp.asarray(MOVES)[action.astype(jnp.int32) % 5], 0, GRID - 1)
    blocked = jnp.zeros((), bool)
    for w, col in enumerate(WALL_COLS):
        passable = (cand[0] == s.door_row[w]) & s.door_open[w]
        blocked = blocked | ((w < n_walls) & (cand[1] == col) & ~passable)
    pos = jnp.where(blocked, s.pos, cand)
    on_key = jnp.stack([
        (pos[0] == s.key_pos[w, 0]) & (pos[1] == s.key_pos[w, 1]) & (w < n_walls) & ~s.key_taken[w]
        for w in range(3)
    ])
    reward = jnp.float32(0.0)
    for w in range(3):  # the program's order of additions
        reward = reward + 0.2 * on_key[w].astype(jnp.float32)
    reward = reward + 0.1 * s.food[pos[0], pos[1]].astype(jnp.float32)
    at_goal = (pos[0] == s.goal[0]) & (pos[1] == s.goal[1])
    reward = reward + at_goal.astype(jnp.float32)
    t = s.t + 1
    new = s._replace(
        pos=pos, door_open=s.door_open | on_key, key_taken=s.key_taken | on_key,
        food=s.food.at[pos[0], pos[1]].set(False), t=t,
    )
    return new, reward, at_goal, (t >= MAX_STEPS) & ~at_goal


def env_step_autoreset(s: Room, action):
    """Same-step auto-reset: a finished instance comes back reset, its true last picture apart."""
    s1, reward, term, trunc = env_step(s, action)
    done = term | trunc
    k_reset, k_carry = jax.random.split(s1.key)
    s1 = s1._replace(key=k_carry)
    fresh = env_reset(k_reset, 0.0)._replace(level=s1.level)
    s2 = jax.tree.map(lambda a, b: jnp.where(done, a, b), fresh, s1)
    return s2, reward, term, trunc, env_render(s1)


# ----------------------------------------------------------------------------
# one dispatch
# ----------------------------------------------------------------------------

def _categorical(logits):
    return logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)


def rollout(params, actor, key, hp, q):
    prep = lambda img: img.astype(jnp.float32) / 255.0  # noqa: E731
    render, step = jax.vmap(env_render), jax.vmap(env_step_autoreset)

    def body(carry, k_step):
        env, ep_ret, ep_len = carry
        img = render(env)
        logits, _ = agent_forward(params, prep(img), q)
        logp_all = _categorical(logits)
        action = jax.random.categorical(jax.random.split(k_step, 1)[0], logp_all, shape=logits.shape[:-1])
        logprob = jnp.take_along_axis(logp_all, action[..., None], -1)[..., 0]
        env, reward, term, trunc, final_img = step(env, action)
        _, v_final = agent_forward(params, prep(final_img), q)
        boot = reward + hp["gamma"] * v_final * trunc.astype(jnp.float32)
        done = term | trunc
        ep_ret, ep_len = ep_ret + reward, ep_len + 1
        out = {
            "rgb": img.reshape(img.shape[0], -1), "actions": action.astype(jnp.float32)[..., None], "logprobs": logprob,
            "rewards": boot, "dones": done.astype(jnp.float32),
            "ep_done": done, "ep_ret": ep_ret, "ep_len": ep_len,
        }
        keep = 1.0 - done.astype(jnp.float32)
        return (env, ep_ret * keep, ep_len * (1 - done.astype(jnp.int32))), out

    keys = jax.random.split(key, hp["rollout_steps"])
    (env, ep_ret, ep_len), traj = lax.scan(body, (actor["env"], actor["ep_ret"], actor["ep_len"]), keys)
    stats = {k: traj.pop(k) for k in ("ep_done", "ep_ret", "ep_len")}
    new_actor = {"env": env, "ep_ret": ep_ret, "ep_len": ep_len, "update": actor["update"] + 1}
    return new_actor, traj, prep(render(env)), stats


def gae(rewards, values, dones, next_value, gamma, lam):
    def back(carry, xs):
        last, nxt = carry
        r, v, nd = xs
        last = r + gamma * nxt * nd - v + gamma * lam * nd * last
        return (last, v), last

    _, adv = lax.scan(back, (jnp.zeros_like(next_value), next_value), (rewards, values, 1.0 - dones), reverse=True)
    return adv + values, adv


def ppo_loss(params, batch, hp, q):
    logits, value = agent_forward(params, batch["rgb"], q)
    logp_all = _categorical(logits)
    logp = jnp.take_along_axis(logp_all, batch["actions"][..., 0].astype(jnp.int32)[..., None], -1)[..., 0]
    entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, -1)
    adv = batch["advantages"]
    adv = (adv - adv.mean()) / (adv.std(ddof=1) + 1e-8)
    ratio = jnp.exp(logp - batch["logprobs"])
    clip = hp["clip_coef"]
    pg = -jnp.minimum(adv * ratio, adv * jnp.clip(ratio, 1 - clip, 1 + clip)).mean()
    v_clip = batch["values"] + jnp.clip(value - batch["values"], -clip, clip)
    vl = 0.5 * jnp.maximum((value - batch["returns"]) ** 2, (v_clip - batch["returns"]) ** 2).mean()
    ent = -entropy.mean()
    return pg + hp["vf_coef"] * vl + hp["ent_coef"] * ent, (pg, vl, ent)


def adam_step(params, grads, mu, nu, count, hp):
    """clip_by_global_norm, then Adam with bias correction (optax's arithmetic)."""
    leaves = jax.tree.leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.where(norm < hp["max_grad_norm"], 1.0, hp["max_grad_norm"] / norm)
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = hp["b1"], hp["b2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda n, g: b2 * n + (1 - b2) * g * g, nu, grads)
    count = count + 1
    c1, c2 = 1 - b1 ** count.astype(jnp.float32), 1 - b2 ** count.astype(jnp.float32)
    params = jax.tree.map(
        lambda p, m, n: p - hp["lr"] * (m / c1) / (jnp.sqrt(n / c2) + hp["eps"]), params, mu, nu
    )
    return params, mu, nu, count


def update(params, mu, nu, count, traj, last_obs, key, hp, q, fault):
    T, B = traj["rewards"].shape
    # the rollout keeps the uint8 pictures, flattened: a last axis of 3 would be padded to 128 lanes on the chip
    prep = lambda flat: flat.reshape(flat.shape[:-1] + (64, 64, 3)).astype(jnp.float32) / 255.0  # noqa: E731
    # values of the whole rollout in blocks of rows, so that the float32 activations fit
    _, values = lax.map(lambda img: agent_forward(params, prep(img), q), traj["rgb"])
    flat_rgb = traj["rgb"].reshape((T * B,) + traj["rgb"].shape[2:])
    _, next_value = agent_forward(params, last_obs, q)
    returns, adv = gae(traj["rewards"], values, traj["dones"], next_value, hp["gamma"], hp["gae_lambda"])
    flat = {
        "rgb": flat_rgb, "actions": traj["actions"].reshape(T * B, -1), "logprobs": traj["logprobs"].reshape(-1),
        "values": values.reshape(-1), "returns": returns.reshape(-1), "advantages": adv.reshape(-1),
    }
    bs, n_mb = hp["batch_size"], hp["num_minibatches"]

    def epoch(carry, key_e):
        perm = jax.random.permutation(key_e, T * B)

        def minibatch(i, c):
            params, mu, nu, count, _ = c
            idx = lax.dynamic_slice(perm, (i * bs,), (bs,))
            if fault == "half_batch":
                idx = idx[: bs // 2]
            batch = {k: jnp.take(v, idx, axis=0) for k, v in flat.items()}
            batch["rgb"] = prep(batch["rgb"])
            (_, losses), grads = jax.value_and_grad(ppo_loss, has_aux=True)(params, batch, hp, q)
            params, mu, nu, count = adam_step(params, grads, mu, nu, count, hp)
            return params, mu, nu, count, losses

        return lax.fori_loop(0, n_mb, minibatch, carry), None

    zeros = (jnp.zeros(()),) * 3
    carry, _ = lax.scan(epoch, (params, mu, nu, count, zeros), jax.random.split(key, hp["update_epochs"]))
    return carry


@partial(jax.jit, static_argnames=("hp_static", "precision", "fault"))
def dispatch(params, mu, nu, count, actor, key, hp_static, precision="f32", fault=None):
    """One fused dispatch as the program's ``ppo.anakin_phase`` defines it."""
    hp = dict(hp_static)
    q = _quantizer(precision)
    k_roll, k_train, k_next = jax.random.split(key, 3)
    actor, traj, last_obs, stats = rollout(params, actor, k_roll, hp, q)
    params, mu, nu, count, losses = update(params, mu, nu, count, traj, last_obs, k_train, hp, q, fault)
    return params, mu, nu, count, actor, k_next, losses, stats
