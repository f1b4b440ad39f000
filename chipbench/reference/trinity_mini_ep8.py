"""Plain float32 reference of the ``trinity_mini_ep8`` configuration: the decoder's forward pass, the
masked PPO loss, its gradients and AdamW, in straightforward ``jax.numpy``.  Nothing is imported from
the program; only its parameter names are shared.

What is plain here and is not in the program: no cache (every token attends over the keys of its whole
episode so far, found by episode number and position, never by a ring's slots), no grouped product (a
loop over the held experts with a dense mask), no fused phases.  The only blocks are those needed to
fit: attention runs one env and one block of queries at a time, and a layer is recomputed in the
backward pass.

It is teacher-forced: it takes the tokens the program sampled (with random weights the largest logit
changes on rounding).  Keys and values of tokens generated under older parameters are constants, as
they are for the program (the recurrent state at a segment's start is data, not a function of the
parameters): ``forward`` returns the keys and values it made and takes those of the past.

``precision="fp8"`` rounds every matmul operand to e4m3 (the control: the precision below the
configuration's bf16-mixed).  ``fault`` plants one of: ``window`` (a sliding layer attends to the whole
episode), ``no_shared`` (the shared expert left out), ``half_batch`` (half of every minibatch left out).
``window`` can also be asked for by the traced flag ``see_all``, which costs no second compile of a program.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

SLIDING = "sliding_attention"
QUERY_BLOCK = 256


def q8(x, precision: str):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32) if precision == "fp8" else x


def mm(a, b, precision: str):
    return jnp.matmul(q8(a, precision), q8(b, precision))


def rms(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotary(x, pos, theta: float):
    """x (T, heads..., D), pos (T,): the two halves of D rotated against each other."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) * inv
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def gated(w, x, precision: str):
    return mm(jax.nn.silu(mm(x, w["w1"], precision)) * mm(x, w["w3"], precision), w["w2"], precision)


def attention(q, k, v, pos_q, ep_q, pos_k, ep_k, window: Optional[int], precision: str):
    """One env: q (T, KV, G, D), k/v (S, KV, D).  A query sees the keys of its own episode that are not
    later than it and, under a window, fewer than ``window`` positions back.  Blocks of queries only."""

    def block(args):
        qb, pq, eq = args
        mask = (ep_k[None] == eq[:, None]) & (pos_k[None] <= pq[:, None])
        if window is not None:
            mask &= pq[:, None] - pos_k[None] < window
        s = jnp.einsum("tkgd,skd->kgts", q8(qb, precision), q8(k, precision)) / math.sqrt(q.shape[-1])
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -1e30), axis=-1)
        return jnp.einsum("kgts,skd->tkgd", q8(p, precision), q8(v, precision))

    T = q.shape[0]
    qb = min(QUERY_BLOCK, T)
    split = lambda z: z.reshape((T // qb, qb) + z.shape[1:])  # noqa: E731
    out = jax.lax.map(block, (split(q), split(pos_q), split(ep_q)))
    return out.reshape((T,) + out.shape[2:])


def experts_part(moe, m, cfg, precision: str, fault: Optional[str]):
    """Sigmoid router over all experts, the k largest of score + bias, weights scale * s / sum s; the
    shared expert and the held experts' part, each held expert over every token under a dense mask."""
    s = jax.nn.sigmoid(mm(m, moe["router"], precision))
    _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(moe["router_bias"]), cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = cfg["route_scale"] * w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    counts = jnp.sum(jax.nn.one_hot(chosen, cfg["num_experts"], dtype=jnp.int32), axis=(0, 1))
    out = jnp.zeros_like(m) if fault == "no_shared" else gated(moe["shared"], m, precision)
    first, held = cfg["experts_held"]

    def one_expert(out, xs):  # every token through this expert, weighted by what the router gave it (mostly nought)
        e, one = xs
        weight = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=-1)
        return out + weight[:, None] * gated(one, m, precision), None

    out, _ = jax.lax.scan(one_expert, out, (jnp.arange(held), moe["experts"]))
    return out, counts


def layer_forward(layer, x, pos, ep, past_k, past_v, past_pos, past_ep, see_all, cfg, sliding, precision, fault):
    """One layer over (B, T, H); past keys and values (B, P, KV, D) are constants.  -> x', counts, k, v.
    ``see_all`` (a traced flag, so that the planted fault needs no second program): a sliding layer ignores its window."""
    eps, D, KV = cfg["rms_norm_eps"], cfg["head_dim"], cfg["num_key_value_heads"]
    G = cfg["num_attention_heads"] // KV
    B, T, H = x.shape
    a = rms(x, layer["norm_in"], eps)
    q = rms(mm(a, layer["wq"], precision).reshape(B, T, KV, G, D), layer["q_norm"], eps)
    k = rms(mm(a, layer["wk"], precision).reshape(B, T, KV, D), layer["k_norm"], eps)
    v = mm(a, layer["wv"], precision).reshape(B, T, KV, D)
    if sliding:
        q = jax.vmap(lambda z, p: rotary(z, p, cfg["rope_theta"]))(q, pos)
        k = jax.vmap(lambda z, p: rotary(z, p, cfg["rope_theta"]))(k, pos)
    window = jnp.where(see_all, 2 ** 30, cfg["sliding_window"]) if sliding else None
    keys, values = jnp.concatenate([past_k, k], 1), jnp.concatenate([past_v, v], 1)
    pos_k, ep_k = jnp.concatenate([past_pos, pos], 1), jnp.concatenate([past_ep, ep], 1)
    one_env = jax.checkpoint(lambda z: attention(*z, window, precision))
    o = jax.lax.map(one_env, (q, keys, values, pos, ep, pos_k, ep_k)).reshape(B, T, -1)
    o = o * jax.nn.sigmoid(mm(a, layer["wg"], precision))
    x = x + rms(mm(o, layer["wo"], precision), layer["norm_post_attn"], eps)
    m = rms(x, layer["norm_pre_mlp"], eps).reshape(B * T, H)
    if "mlp" in layer:
        f, counts = gated(layer["mlp"], m, precision), None
    else:
        f, counts = experts_part(layer["moe"], m, cfg, precision, fault)
    return x + rms(f, layer["norm_post_mlp"], eps).reshape(B, T, H), counts, k, v


def forward(params, cfg: Dict[str, Any], tokens, pos, ep, past, precision: str = "f32", fault: Optional[str] = None,
            see_all=False):
    """tokens, pos, ep (B, T) on ``past`` = {"k": [per layer (B, P, KV, D)], "v": [...], "pos", "ep" (B, P)}
    -> logits (B, T, V), values (B, T), router counts (expert layers, E), [(k, v)] of these tokens per layer.
    ``ep`` numbers an env's episodes (-1: padding, seen by no real query)."""
    x = params["embed"][tokens] * math.sqrt(cfg["hidden_size"])
    counts, made = [], []
    for i, kind in enumerate(cfg["layer_types"]):
        run = jax.checkpoint(layer_forward, static_argnums=(9, 10, 11, 12))
        x, c, k, v = run(
            params[f"layer_{i}"], x, pos, ep, past["k"][i], past["v"][i], past["pos"], past["ep"],
            jnp.logical_or(see_all, fault == "window"), _Static(cfg), kind == SLIDING, precision, fault,
        )
        made.append((k, v))
        if c is not None:
            counts.append(c)
    h = rms(x, params["norm_out"], cfg["rms_norm_eps"])
    return mm(h, params["head"], precision), mm(h, params["value_head"], precision)[..., 0], jnp.stack(counts), made


class _Static(dict):
    """The configuration as a hashable static argument."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


def empty_past(cfg: Dict[str, Any], batch: int) -> Dict[str, Any]:
    shape = (batch, 0, cfg["num_key_value_heads"], cfg["head_dim"])
    n = len(cfg["layer_types"])
    return {"k": [jnp.zeros(shape)] * n, "v": [jnp.zeros(shape)] * n,
            "pos": jnp.zeros((batch, 0), jnp.int32), "ep": jnp.zeros((batch, 0), jnp.int32)}


# ----------------------------------------------------------------------------
# the loss, its gradients, AdamW, the selection bias
# ----------------------------------------------------------------------------

def gae(rewards, values, dones, last_value, gamma: float, lam: float):
    """(T, B) arrays; ``dones[t]``: the episode ended at step t."""
    def back(carry, xs):
        adv_next, v_next = carry
        r, v, d = xs
        delta = r + gamma * v_next * (1.0 - d) - v
        adv = delta + gamma * lam * (1.0 - d) * adv_next
        return (adv, v), adv

    _, adv = jax.lax.scan(back, (jnp.zeros_like(last_value), last_value), (rewards, values, dones), reverse=True)
    return adv + values, adv


def masked_mean(x, mask):
    return jnp.sum(x * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def ppo_loss(params, cfg, hp, batch, past, precision, fault, see_all=False):
    """Masked PPO loss of one minibatch (arrays (B, T)): clipped surrogate, plain squared value error,
    entropy, each a mean over the steps whose mask is 1."""
    logits, values, counts, _ = forward(params, cfg, batch["tokens"], batch["pos"], batch["ep"], past, precision, fault, see_all)
    logp_all = jax.nn.log_softmax(logits, axis=-1)
    logp = jnp.take_along_axis(logp_all, batch["actions"][..., None], axis=-1)[..., 0]
    entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1)
    ratio = jnp.exp(logp - batch["old_logp"])
    adv, mask = batch["advantages"], batch["mask"]
    pg = masked_mean(-jnp.minimum(adv * ratio, adv * jnp.clip(ratio, 1 - hp["clip_coef"], 1 + hp["clip_coef"])), mask)
    vl = masked_mean((values - batch["returns"]) ** 2, mask)
    el = masked_mean(-entropy, mask)
    return pg + hp["vf_coef"] * vl + hp["ent_coef"] * el, (jnp.stack([pg, vl, el]), counts)


def adamw_step(params, grads, mu, nu, count, hp):
    """clip_by_global_norm, Adam with bias correction, decoupled weight decay (optax's arithmetic)."""
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.where(norm < hp["max_grad_norm"], 1.0, hp["max_grad_norm"] / norm)
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = hp["b1"], hp["b2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda n, g: b2 * n + (1 - b2) * g * g, nu, grads)
    count = count + 1
    c1, c2 = 1 - b1 ** count.astype(jnp.float32), 1 - b2 ** count.astype(jnp.float32)
    params = jax.tree.map(
        lambda p, m, n: p - hp["lr"] * ((m / c1) / (jnp.sqrt(n / c2) + hp["eps"]) + hp["weight_decay"] * p),
        params, mu, nu,
    )
    return params, mu, nu, count


def bias_step(params, counts, cfg):
    """b += coeff * sign(mean load - load_e) over the router's counts of this update, every expert layer."""
    out = dict(params)
    moe_layers = [i for i in range(len(cfg["layer_types"])) if i >= cfg["num_dense_layers"]]
    for row, i in enumerate(moe_layers):
        load = counts[row].astype(jnp.float32)
        layer = dict(out[f"layer_{i}"])
        layer["moe"] = dict(layer["moe"], router_bias=layer["moe"]["router_bias"]
                            + cfg["load_balance_coeff"] * jnp.sign(load.mean() - load))
        out[f"layer_{i}"] = layer
    return out


@partial(jax.jit, static_argnames=("cfg", "hp", "precision", "fault"), donate_argnums=(0, 1, 2))
def update(params, mu, nu, count, batch, past, see_all, cfg, hp, precision="f32", fault=None):
    """One minibatch: the loss's gradients, AdamW, the bias rule.
    ``batch`` arrays are (B, T), ``past`` the same envs' constants.  -> params, mu, nu, count, losses, counts."""
    cfg_d, hp_d = dict(cfg), dict(hp)
    if fault == "half_batch":
        half = batch["tokens"].shape[0] // 2
        batch, past = jax.tree.map(lambda z: z[:half], (batch, past))
    (_, (losses, counts)), grads = jax.value_and_grad(ppo_loss, has_aux=True)(
        params, cfg_d, hp_d, batch, past, precision, fault, see_all
    )
    params, mu, nu, count = adamw_step(params, grads, mu, nu, count, hp_d)
    return bias_step(params, counts, cfg_d), mu, nu, count, losses, counts


@partial(jax.jit, static_argnames=("cfg", "precision", "fault"))
def forward_jit(params, tokens, pos, ep, past, see_all, cfg, precision="f32", fault=None):
    return forward(params, dict(cfg), tokens, pos, ep, past, precision, fault, see_all)


@partial(jax.jit, donate_argnums=(0,))
def extend_past(past, made, pos, ep, offset):
    """``past`` with the keys and values ``made`` for tokens at ``pos``/``ep`` (B, T) written from column ``offset`` on."""
    put = lambda whole, part: jax.lax.dynamic_update_slice_in_dim(whole, part.astype(whole.dtype), offset, axis=1)  # noqa: E731
    return {"k": [put(a, k) for a, (k, _) in zip(past["k"], made)], "v": [put(a, v) for a, (_, v) in zip(past["v"], made)],
            "pos": put(past["pos"], pos), "ep": put(past["ep"], ep)}


def positions(is_first, pos0, ep0):
    """(T, B) ``is_first`` (a reset before the step) -> position in the episode and episode number of every step."""
    def fwd(carry, first):
        pos, ep = carry
        pos = jnp.where(first > 0, 0, pos)
        ep = ep + (first > 0).astype(jnp.int32)
        return (pos + 1, ep), (pos, ep)

    _, (pos, ep) = jax.lax.scan(fwd, (pos0.astype(jnp.int32), ep0.astype(jnp.int32)), is_first)
    return pos, ep


def history(prompt, prompt_len, t, length: int):
    """The token env's episode so far as a faultless copier leaves it: the prompt, then the prompt over
    and over, each token seen one step after it was emitted.  prompt (B, P), prompt_len, t (B,) -> (B, length)."""
    i = jnp.arange(length)[None]
    copied = jnp.take_along_axis(prompt, jnp.mod(jnp.maximum(i - 1, 0), prompt_len[:, None]), axis=1)
    own = jnp.take_along_axis(prompt, jnp.minimum(i, prompt.shape[1] - 1), axis=1)
    return jnp.where(i < prompt_len[:, None], own, copied)


@jax.jit
def leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


@jax.jit
def change_norms(after, before):
    return jax.tree.map(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))), after, before)
