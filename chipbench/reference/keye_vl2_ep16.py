"""Plain float32 reference of the ``keye_vl2_ep16`` configuration: the forward pass of a decoder whose every layer is
attention over the keys a learned indexer selects followed by a sparse feed-forward, the masked PPO loss with the
indexer's own loss L_I beside it, their gradients and AdamW, in straightforward ``jax.numpy``.  Nothing is imported
from the program; only its parameter names are shared.

The equations, per layer on rows ``x`` of width H (Keye-VL-2.0-30B-A3B's language model, ``model_type`` ``KeyeVL2``,
the Qwen3-VL-MoE text block of ``transformers`` 4.57.6, ``models/qwen3_vl_moe/modeling_qwen3_vl_moe.py``; the
indexer is DeepSeek-V3.2-Exp's lightning indexer as its technical report describes it, of which that package has no
code):

* ``a = rms(x)``; ``q = a Wq`` (32 heads of 128), ``k, v = a Wk, a Wv`` (4 heads of 128), an RMS norm per head on
  ``q`` and ``k``, rotary positions over all 128 lanes at ``rope_theta`` (``mrope_section`` gives a text token one
  position in all three sections: plain rotary);
* the indexer, on ``a`` read as a constant: ``qI_j = (a WqI)_j`` (16 heads of 64), ``kI = LayerNorm(a WkI)`` (one head
  of 64, cached per position), rotary positions on their first ``index_rope_dim`` lanes, ``w_j = (a Ww)_j / sqrt(16)``;
  **``I[t, s] = sum_j w_j relu(qI_j[t] . kI[s]) / sqrt(64)``** over the positions ``s <= t`` of the token's episode;
* ``S_t``: the ``topk`` largest ``I[t, s]`` (every position where ``t < topk``), ties to the lower position, a score
  of nought counted as +0; all 32 heads of token ``t`` attend over ``S_t`` and no other key; ``x <- x + o Wo``;
* ``m = rms(x)``; ``p = softmax(m W_r)`` in float32 over all 128 experts; the 8 largest, weights ``p_e / sum of the
  selected``; each expert ``(silu(m W1) * (m W3)) W2``; no shared expert, no selection bias; ``x <- x + f``;
* **``L_I = KL(p_t || softmax(I[t, S_t]))``** a token, ``p_t`` the main attention's probabilities over ``S_t`` summed
  over its heads and normalised (a constant); averaged over the steps the PPO terms average over, summed over the
  layers, and added to the PPO loss at weight 1.  The policy loss trains everything but the indexer, L_I the indexer
  alone.

Departures from the published description, each also in ``chipbench/configs/keye_vl2_30b_ep16.json``:

* the indexer's equations, its rotary split (the first half of the index head), the LayerNorm on ``kI`` and ``qI``
  from the normed row are DeepSeek-V3.2's as recalled; its Hadamard rotation of ``qI`` and ``kI`` is left out (it is
  orthogonal: every dot product is as it was), and so are its fp8 index products; ``q_chunk_size`` and
  ``kv_chunk_size`` are one implementation's tiling and not mathematics;
* only the experts ``experts_held`` are computed (this chip's share; what the others would add is left out);
* the position is the position in the episode, and a token sees no other episode's keys;
* a value head beside the language head (PPO's critic; the model has none).

What is plain here and is not in the program: no cache (every token finds the keys and index keys of its whole
episode so far by episode number and position among everything the env has seen), the selection by sorting
(``lax.top_k``) and a dense mask, no gathered read, no grouped product (a loop over the held experts with a dense
mask), no fused phases.  The only blocks are those needed to fit: attention runs one env and one block of queries at
a time, and a layer is recomputed in the backward pass.

It is teacher-forced: it takes the tokens the program sampled.  Keys, values and index keys of tokens generated under
older parameters are constants, as they are for the program's carry: ``forward`` returns those it made and takes
those of the past, and besides each layer's selection, as a mask over the past's columns and the segment's.

``precision="fp8"`` rounds every matmul operand, the indexer's among them, to e4m3 (the control: the precision below
the configuration's bf16-mixed).  ``fault_code`` (traced, so that none costs a compile of its own) plants one of
``FAULT_CODES``: ``recent_keys`` (the ``topk`` newest positions instead of the indexer's choice), ``dense_keys`` (no
selection: every position of the episode), ``no_index_loss`` (L_I left out: the indexer takes no gradient);
``fault="half_batch"`` leaves half of every minibatch out.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

QUERY_BLOCK = 64
FAULT_CODES = {"recent_keys": 1, "dense_keys": 2, "no_index_loss": 3}


def q8(x, precision: str):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32) if precision == "fp8" else x


def mm(a, b, precision: str):
    return jnp.matmul(q8(a, precision), q8(b, precision))


def rms(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps: float):
    c = x - jnp.mean(x, axis=-1, keepdims=True)
    return c * jax.lax.rsqrt(jnp.mean(c * c, axis=-1, keepdims=True) + eps) * w + b


def rotary(x, pos, theta: float):
    """x (T, heads..., D), pos (T,): the two halves of D rotated against each other."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) * inv
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def gated(w, x, precision: str):
    return mm(jax.nn.silu(mm(x, w["w1"], precision)) * mm(x, w["w3"], precision), w["w2"], precision)


def exact_top(scores, visible, k: int):
    """The ``k`` largest ``scores`` (..., S) among the ``visible``, ties to the lower index, as a mask (every visible
    one where there are no more): the ``k``-th largest by ``lax.top_k``, the ties at it counted off lowest first."""
    k = min(k, scores.shape[-1])
    masked = jnp.where(visible, scores, -jnp.inf)
    kth = jax.lax.top_k(masked, k)[0][..., -1:]
    above = visible & (masked > kth)
    tie = visible & (masked == kth)
    need = k - jnp.sum(above, axis=-1, keepdims=True)
    return above | (tie & (jnp.cumsum(tie, axis=-1) <= need))


def attention(q, k, v, qi, wi, ik, pos_q, ep_q, pos_k, ep_k, topk: int, precision: str, fault_code):
    """One env: q (T, KV, G, D), k/v (S, KV, D), index queries qi (T, h, d) and weights wi (T, h), index keys ik (S, d).
    A query sees the keys of its own episode that are not later than it, and attends over the ``topk`` its index
    scores select.  -> o (T, KV, G, D), L_I (T,), the selection (T, S)."""

    def block(args):
        qb, qib, wib, pq, eq = args
        visible = (ep_k[None] == eq[:, None]) & (pos_k[None] <= pq[:, None])
        dots = jnp.einsum("thd,sd->ths", q8(qib, precision), q8(ik, precision))
        index = jnp.sum(wib[..., None] * jax.nn.relu(dots), axis=1) / math.sqrt(qi.shape[-1])
        index = jnp.where(index == 0, 0.0, index)
        sel = exact_top(index, visible, topk)
        sel = jnp.where(fault_code == 1, visible & (pos_k[None] > pq[:, None] - topk), sel)
        sel = jnp.where(fault_code == 2, visible, sel)
        s = jnp.einsum("tkgd,skd->kgts", q8(qb, precision), q8(k, precision)) / math.sqrt(q.shape[-1])
        p = jax.nn.softmax(jnp.where(sel[None, None], s, -1e30), axis=-1)
        o = jnp.einsum("kgts,skd->tkgd", q8(p, precision), q8(v, precision))
        p_bar = jax.lax.stop_gradient(jnp.mean(p, axis=(0, 1)))
        log_q = jax.nn.log_softmax(jnp.where(sel, index, -1e30), axis=-1)
        kl = jnp.sum(jnp.where(sel, jax.scipy.special.xlogy(p_bar, p_bar) - p_bar * log_q, 0.0), axis=-1)
        return o, kl, sel

    T = q.shape[0]
    qb = math.gcd(T, QUERY_BLOCK)
    split = lambda z: z.reshape((T // qb, qb) + z.shape[1:])  # noqa: E731
    o, kl, sel = jax.lax.map(block, (split(q), split(qi), split(wi), split(pos_q), split(ep_q)))
    return o.reshape((T,) + o.shape[2:]), kl.reshape(T), sel.reshape((T,) + sel.shape[2:])


def experts_part(moe, m, cfg, precision: str):
    """Softmax router over all experts, the k largest, weights p / sum of the selected; the held experts' part,
    each held expert over every token under a dense mask."""
    p = jax.nn.softmax(mm(m, moe["router"], precision), axis=-1)
    _, chosen = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(p, chosen, axis=-1)
    w = w / w.sum(axis=-1, keepdims=True)
    counts = jnp.sum(jax.nn.one_hot(chosen, cfg["num_experts"], dtype=jnp.int32), axis=(0, 1))
    first, held = cfg["experts_held"]

    def one_expert(out, xs):  # every token through this expert, weighted by what the router gave it (mostly nought)
        e, one = xs
        weight = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=-1)
        return out + weight[:, None] * gated(one, m, precision), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(m), (jnp.arange(held), moe["experts"]))
    return out, counts


def layer_forward(layer, x, pos, ep, past, past_pos, past_ep, fault_code, cfg, precision):
    """One layer over (B, T, H); ``past`` (keys, values (B, P, KV, D), index keys (B, P, d)) are constants.
    -> x', counts, (k, v, index keys) of these tokens, L_I (B, T), the selection (B, T, P + T)."""
    eps, D, KV = cfg["rms_norm_eps"], cfg["head_dim"], cfg["num_key_value_heads"]
    G, h, d, r = cfg["num_attention_heads"] // KV, cfg["index_heads"], cfg["index_head_dim"], cfg["index_rope_dim"]
    B, T, H = x.shape
    theta = cfg["rope_theta"]
    a = rms(x, layer["norm_in"], eps)
    q = rms(mm(a, layer["wq"], precision).reshape(B, T, KV, G, D), layer["q_norm"], eps)
    k = rms(mm(a, layer["wk"], precision).reshape(B, T, KV, D), layer["k_norm"], eps)
    v = mm(a, layer["wv"], precision).reshape(B, T, KV, D)
    q = jax.vmap(lambda z, p: rotary(z, p, theta))(q, pos)
    k = jax.vmap(lambda z, p: rotary(z, p, theta))(k, pos)
    index, a_c = layer["index"], jax.lax.stop_gradient(a)
    qi = mm(a_c, index["wq"], precision).reshape(B, T, h, d)
    ki = layer_norm(mm(a_c, index["wk"], precision), index["norm"], index["norm_bias"], eps)
    wi = mm(a_c, index["ww"], precision) / math.sqrt(h)
    part = lambda z: jnp.concatenate([jax.vmap(lambda y, p: rotary(y, p, theta))(z[..., :r], pos), z[..., r:]], -1)  # noqa: E731
    qi, ki = part(qi), part(ki)
    keys, values = jnp.concatenate([past[0], k], 1), jnp.concatenate([past[1], v], 1)
    index_keys = jnp.concatenate([past[2], ki], 1)
    pos_k, ep_k = jnp.concatenate([past_pos, pos], 1), jnp.concatenate([past_ep, ep], 1)
    one_env = jax.checkpoint(lambda z: attention(*z, cfg["index_topk"], precision, fault_code))
    o, kl, sel = jax.lax.map(one_env, (q, keys, values, qi, wi, index_keys, pos, ep, pos_k, ep_k))
    x = x + mm(o.reshape(B, T, -1), layer["wo"], precision)
    f, counts = experts_part(layer["moe"], rms(x, layer["norm_pre_mlp"], eps).reshape(B * T, H), cfg, precision)
    return x + f.reshape(B, T, H), counts, (k, v, ki), kl, sel


def forward(params, cfg: Dict[str, Any], tokens, pos, ep, past, precision: str = "f32", fault_code=0):
    """tokens, pos, ep (B, T) on ``past`` = {"layers": [per layer (k, v, index keys)], "pos", "ep" (B, P)} -> logits
    (B, T, V), values (B, T), router counts (layers, E), what each layer made of these tokens (as ``past["layers"]``),
    L_I (B, T) summed over the layers, each layer's selection (B, T, P + T).  ``ep`` numbers an env's episodes (-1:
    padding, seen by no real query)."""
    x = params["embed"][tokens]
    counts, made, kl, sel = [], [], 0.0, []
    for i in range(len(cfg["layer_types"])):
        run = jax.checkpoint(layer_forward, static_argnums=(8, 9))
        x, c, m, layer_kl, layer_sel = run(params[f"layer_{i}"], x, pos, ep, past["layers"][i], past["pos"], past["ep"],
                                           jnp.asarray(fault_code, jnp.int32), _Static(cfg), precision)
        counts.append(c)
        made.append(m)
        kl = kl + layer_kl
        sel.append(layer_sel)
    h = rms(x, params["norm_out"], cfg["rms_norm_eps"])
    return mm(h, params["head"], precision), mm(h, params["value_head"], precision)[..., 0], jnp.stack(counts), made, kl, sel


class _Static(dict):
    """The configuration as a hashable static argument."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


def empty_past(cfg: Dict[str, Any], batch: int, length: int = 0) -> Dict[str, Any]:
    kv = (batch, length, cfg["num_key_value_heads"], cfg["head_dim"])
    ik = (batch, length, cfg["index_head_dim"])
    return {"layers": [(jnp.zeros(kv), jnp.zeros(kv), jnp.zeros(ik)) for _ in cfg["layer_types"]],
            "pos": jnp.zeros((batch, length), jnp.int32), "ep": jnp.full((batch, length), -1, jnp.int32)}


# ----------------------------------------------------------------------------
# the loss, its gradients, AdamW
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


def ppo_loss(params, cfg, hp, batch, past, precision, fault_code=0):
    """Masked PPO loss of one minibatch (arrays (B, T)): clipped surrogate, plain squared value error, entropy, each a
    mean over the steps whose mask is 1, and L_I averaged over the same steps (nought under ``no_index_loss``)."""
    logits, values, counts, _, kl, _ = forward(params, cfg, batch["tokens"], batch["pos"], batch["ep"], past, precision, fault_code)
    logp_all = jax.nn.log_softmax(logits, axis=-1)
    logp = jnp.take_along_axis(logp_all, batch["actions"][..., None], axis=-1)[..., 0]
    entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1)
    ratio = jnp.exp(logp - batch["old_logp"])
    adv, mask = batch["advantages"], batch["mask"]
    pg = masked_mean(-jnp.minimum(adv * ratio, adv * jnp.clip(ratio, 1 - hp["clip_coef"], 1 + hp["clip_coef"])), mask)
    vl = masked_mean((values - batch["returns"]) ** 2, mask)
    el = masked_mean(-entropy, mask)
    index_loss = jnp.where(fault_code == 3, 0.0, masked_mean(kl, mask))
    return pg + hp["vf_coef"] * vl + hp["ent_coef"] * el + index_loss, (jnp.stack([pg, vl, el]), counts)


def adamw_step(params, grads, mu, nu, count, hp):
    """clip_by_global_norm, Adam with bias correction, decoupled weight decay on every leaf (optax's arithmetic)."""
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


@partial(jax.jit, static_argnames=("cfg", "hp", "precision", "fault"), donate_argnums=(0, 1, 2))
def update(params, mu, nu, count, batch, past, fault_code, cfg, hp, precision="f32", fault: Optional[str] = None):
    """One minibatch: the loss's gradients, AdamW (a softmax router has no selection bias to move).
    ``batch`` arrays are (B, T), ``past`` the same envs' constants.  -> params, mu, nu, count, losses, counts."""
    cfg_d, hp_d = dict(cfg), dict(hp)
    if fault == "half_batch":
        half = batch["tokens"].shape[0] // 2
        batch, past = jax.tree.map(lambda z: z[:half], (batch, past))
    (_, (losses, counts)), grads = jax.value_and_grad(ppo_loss, has_aux=True)(
        params, cfg_d, hp_d, batch, past, precision, fault_code)
    params, mu, nu, count = adamw_step(params, grads, mu, nu, count, hp_d)
    return params, mu, nu, count, losses, counts


@partial(jax.jit, static_argnames=("cfg", "precision", "fault"))
def forward_jit(params, tokens, pos, ep, past, fault_code, cfg, precision="f32", fault: Optional[str] = None):
    return forward(params, dict(cfg), tokens, pos, ep, past, precision, fault_code)


@partial(jax.jit, donate_argnums=(0,))
def extend_past(past, made, pos, ep, offset):
    """``past`` with what the layers ``made`` of tokens at ``pos``/``ep`` (B, T) written from column ``offset`` on."""
    put = lambda whole, part: jax.lax.dynamic_update_slice_in_dim(whole, part.astype(whole.dtype), offset, axis=1)  # noqa: E731
    return {"layers": [tuple(put(w, p) for w, p in zip(old, new)) for old, new in zip(past["layers"], made)],
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
