"""Plain float32 reference of the ``lfm2_24b_ep8`` configuration: the hybrid decoder's forward pass (gated
short convolutions beside grouped-query attention, a dense and then sparse feed-forwards), the masked PPO
loss, its gradients and AdamW, in straightforward ``jax.numpy``.  Nothing is imported from the program; only
its parameter names are shared.

The equations, per layer on rows ``x`` of width H, every projection without bias (LiquidAI LFM2-24B-A2B,
``model_type`` ``lfm2_moe``; the mixers, norms and the layer's order are the dense sibling's
``transformers/models/lfm2/modeling_lfm2.py``, 4.57.6: ``Lfm2ShortConv.slow_forward``, ``Lfm2Attention``,
``Lfm2DecoderLayer``):

* ``x = x + mixer(rms(x; norm_in))``, then ``x = x + ff(rms(x; norm_pre_mlp))`` (``operator_norm``,
  ``ffn_norm``); after the last layer ``rms(x; norm_out)`` (``embedding_norm``), then the head.  No norm
  after a mixer or a feed-forward, no embedding multiplier.
* conv mixer: ``[B, C, u] = a W_in``, ``z = B * u``, ``c_t = sum_j conv_w[L - 1 - j] * z_{t - j}`` over the
  ``L = conv_L_cache`` taps ``j = 0 .. L - 1`` (depthwise and causal; ``conv_w[k]`` is the published
  ``conv.weight[:, 0, k]``; no bias), ``y = (C * c) W_out``.
* attention mixer: ``q, k, v = a Wq, a Wk, a Wv``; RMS norm of ``q`` and ``k`` per head; rotary positions
  on both (halves rotated against each other); causal softmax over the whole episode; ``o Wo``.  No gate.
* dense feed-forward: ``(silu(m W1) * (m W3)) W2``.  Sparse: ``s = sigmoid(m W_r)`` over all experts, the
  ``k`` experts with the largest ``s + bias`` (the bias takes no gradient), weights
  ``route_scale * s_e / (sum of the selected s + 1e-6)``, each expert a gated feed-forward; no shared expert.

Departures from the published description, each also in ``chipbench/configs/lfm2_24b_ep8.json``:

* the sparse block is written from the config's keys (``use_expert_bias``, ``norm_topk_prob``,
  ``routed_scaling_factor``) and from the published ``Lfm2MoeSparseMoeBlock`` as ISSUE 34's writer recalls
  it: the installed ``transformers`` has no ``lfm2_moe``;
* only the experts ``experts_held`` are computed (this chip's share; what the others would add is left out);
* the position is the position in the episode, and a token neither attends to nor convolves over another
  episode's tokens (the model's own code has one sequence a row and no resets);
* a value head beside the language head (PPO's critic; the model has none) and an untied head.

What is plain here and is not in the program: no cache and no window of gated inputs (every token finds the
keys of its whole episode so far, and the gated inputs of the ``L - 1`` tokens before it, by episode number
and position among everything the env has seen), no grouped product (a loop over the held experts with a
dense mask), no fused phases.  The only blocks are those needed to fit: attention runs one env and one
block of queries at a time, and a layer is recomputed in the backward pass.

It is teacher-forced: it takes the tokens the program sampled (with random weights the largest logit
changes on rounding).  Keys, values and gated inputs of tokens generated under older parameters are
constants, as they are for the program (the recurrent state at a segment's start is data, not a function of
the parameters): ``forward`` returns what it made of them and takes those of the past.

``precision="fp8"`` rounds every matmul operand to e4m3 (the control: the precision below the
configuration's bf16-mixed).  ``fault`` plants one of: ``conv_prefix`` (a tap that reaches before the
segment reads nought, where the past's rows belong), ``conv_reset`` (a tap inside the segment reads the
token before it whatever its episode), ``half_batch`` (half of every minibatch left out).  The two conv
faults are asked for by the traced ``fault_code`` (1 and 2), so that neither costs a compile of its own.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

CONV = "conv"
QUERY_BLOCK = 256
FAULT_CODES = {"conv_prefix": 1, "conv_reset": 2}
ROUTE_EPS = 1e-6


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


def attention(q, k, v, pos_q, ep_q, pos_k, ep_k, precision: str):
    """One env: q (T, KV, G, D), k/v (S, KV, D).  A query sees the keys of its own episode that are not
    later than it.  Blocks of queries only."""

    def block(args):
        qb, pq, eq = args
        mask = (ep_k[None] == eq[:, None]) & (pos_k[None] <= pq[:, None])
        s = jnp.einsum("tkgd,skd->kgts", q8(qb, precision), q8(k, precision)) / math.sqrt(q.shape[-1])
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -1e30), axis=-1)
        return jnp.einsum("kgts,skd->tkgd", q8(p, precision), q8(v, precision))

    T = q.shape[0]
    qb = min(QUERY_BLOCK, T)
    split = lambda z: z.reshape((T // qb, qb) + z.shape[1:])  # noqa: E731
    out = jax.lax.map(block, (split(q), split(pos_q), split(ep_q)))
    return out.reshape((T,) + out.shape[2:])


def short_conv(z, w, pos_q, ep_q, z_all, pos_k, ep_k, fault_code):
    """One env: the gated inputs ``z`` (T, H) of the segment, ``z_all`` (S, H) those of everything the env has
    seen (the past's, then the segment's own), ``w`` (L, H).  Tap ``j`` of a token reads the gated input of the
    token of its own episode ``j`` positions before it, wherever that lies, and nought where there is none."""
    T, S, L = z.shape[0], z_all.shape[0], w.shape[0]
    t = jnp.arange(T)
    own = jnp.arange(S) >= S - T  # the segment's own columns
    out = w[L - 1] * z
    for j in range(1, L):
        match = (ep_k[None] == ep_q[:, None]) & (pos_k[None] == pos_q[:, None] - j)  # (T, S): at most one a row
        match = jnp.where(fault_code == 1, match & own[None], match)
        before = (jnp.arange(S)[None] == S - T + t[:, None] - j) & (t[:, None] >= j)  # the token j before, whatever it is
        match = jnp.where((fault_code == 2) & (t[:, None] >= j), before, match)
        found = jnp.take(z_all, jnp.argmax(match, axis=-1), axis=0)
        out = out + w[L - 1 - j] * jnp.where(match.any(axis=-1)[:, None], found, 0.0)
    return out


def experts_part(moe, m, cfg, precision: str):
    """Sigmoid router over all experts, the k largest of score + bias, weights scale * s / (sum s + 1e-6);
    the held experts' part, each held expert over every token under a dense mask.  No shared expert."""
    s = jax.nn.sigmoid(mm(m, moe["router"], precision))
    _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(moe["router_bias"]), cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = cfg["route_scale"] * w / (w.sum(axis=-1, keepdims=True) + ROUTE_EPS)
    counts = jnp.sum(jax.nn.one_hot(chosen, cfg["num_experts"], dtype=jnp.int32), axis=(0, 1))
    first, held = cfg["experts_held"]

    def one_expert(out, xs):  # every token through this expert, weighted by what the router gave it (mostly nought)
        e, one = xs
        weight = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=-1)
        return out + weight[:, None] * gated(one, m, precision), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(m), (jnp.arange(held), moe["experts"]))
    return out, counts


def layer_forward(layer, x, pos, ep, past, past_pos, past_ep, fault_code, cfg, kind, precision):
    """One layer over (B, T, H); ``past`` is this layer's constants: keys and values (B, P, KV, D) of an
    attention layer, gated inputs (B, P, H) of a conv layer.  -> x', counts, what the layer made of the
    segment (the same tuple as ``past``)."""
    eps, D, KV = cfg["rms_norm_eps"], cfg["head_dim"], cfg["num_key_value_heads"]
    G = cfg["num_attention_heads"] // KV
    B, T, H = x.shape
    a = rms(x, layer["norm_in"], eps)
    pos_k, ep_k = jnp.concatenate([past_pos, pos], 1), jnp.concatenate([past_ep, ep], 1)
    if kind == CONV:
        gate_in, gate_out, u = jnp.split(mm(a, layer["w_in"], precision), 3, axis=-1)
        z = gate_in * u
        z_all = jnp.concatenate([past[0], z], 1)
        c = jax.vmap(lambda *one: short_conv(*one, fault_code), in_axes=(0, None, 0, 0, 0, 0, 0))(
            z, layer["conv_w"], pos, ep, z_all, pos_k, ep_k)
        x = x + mm(gate_out * c, layer["w_out"], precision)
        made = (z,)
    else:
        q = rms(mm(a, layer["wq"], precision).reshape(B, T, KV, G, D), layer["q_norm"], eps)
        k = rms(mm(a, layer["wk"], precision).reshape(B, T, KV, D), layer["k_norm"], eps)
        v = mm(a, layer["wv"], precision).reshape(B, T, KV, D)
        q = jax.vmap(lambda z, p: rotary(z, p, cfg["rope_theta"]))(q, pos)
        k = jax.vmap(lambda z, p: rotary(z, p, cfg["rope_theta"]))(k, pos)
        keys, values = jnp.concatenate([past[0], k], 1), jnp.concatenate([past[1], v], 1)
        one_env = jax.checkpoint(lambda z: attention(*z, precision))
        o = jax.lax.map(one_env, (q, keys, values, pos, ep, pos_k, ep_k)).reshape(B, T, -1)
        x = x + mm(o, layer["wo"], precision)
        made = (k, v)
    m = rms(x, layer["norm_pre_mlp"], eps).reshape(B * T, H)
    if "mlp" in layer:
        f, counts = gated(layer["mlp"], m, precision), None
    else:
        f, counts = experts_part(layer["moe"], m, cfg, precision)
    return x + f.reshape(B, T, H), counts, made


def forward(params, cfg: Dict[str, Any], tokens, pos, ep, past, precision: str = "f32", fault_code=0):
    """tokens, pos, ep (B, T) on ``past`` = {"layers": [per layer (k, v) or (z,), each (B, P, ...)], "pos", "ep"
    (B, P)} -> logits (B, T, V), values (B, T), router counts (expert layers, E), what each layer made of
    these tokens (as ``past["layers"]``).  ``ep`` numbers an env's episodes (-1: padding, seen by no real query)."""
    x = params["embed"][tokens]
    counts, made = [], []
    for i, kind in enumerate(cfg["layer_types"]):
        run = jax.checkpoint(layer_forward, static_argnums=(8, 9, 10))
        x, c, m = run(params[f"layer_{i}"], x, pos, ep, past["layers"][i], past["pos"], past["ep"],
                      jnp.asarray(fault_code, jnp.int32), _Static(cfg), kind, precision)
        made.append(m)
        if c is not None:
            counts.append(c)
    h = rms(x, params["norm_out"], cfg["rms_norm_eps"])
    return mm(h, params["head"], precision), mm(h, params["value_head"], precision)[..., 0], jnp.stack(counts), made


class _Static(dict):
    """The configuration as a hashable static argument."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


def empty_past(cfg: Dict[str, Any], batch: int, length: int = 0) -> Dict[str, Any]:
    kv = (batch, length, cfg["num_key_value_heads"], cfg["head_dim"])
    layers = [(jnp.zeros((batch, length, cfg["hidden_size"])),) if kind == CONV else (jnp.zeros(kv), jnp.zeros(kv))
              for kind in cfg["layer_types"]]
    return {"layers": layers, "pos": jnp.zeros((batch, length), jnp.int32), "ep": jnp.full((batch, length), -1, jnp.int32)}


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


def ppo_loss(params, cfg, hp, batch, past, precision, fault_code=0):
    """Masked PPO loss of one minibatch (arrays (B, T)): clipped surrogate, plain squared value error,
    entropy, each a mean over the steps whose mask is 1."""
    logits, values, counts, _ = forward(params, cfg, batch["tokens"], batch["pos"], batch["ep"], past, precision, fault_code)
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
def update(params, mu, nu, count, batch, past, fault_code, cfg, hp, precision="f32", fault: Optional[str] = None):
    """One minibatch: the loss's gradients, AdamW, the bias rule.
    ``batch`` arrays are (B, T), ``past`` the same envs' constants.  -> params, mu, nu, count, losses, counts."""
    cfg_d, hp_d = dict(cfg), dict(hp)
    if fault == "half_batch":
        half = batch["tokens"].shape[0] // 2
        batch, past = jax.tree.map(lambda z: z[:half], (batch, past))
    (_, (losses, counts)), grads = jax.value_and_grad(ppo_loss, has_aux=True)(
        params, cfg_d, hp_d, batch, past, precision, fault_code
    )
    params, mu, nu, count = adamw_step(params, grads, mu, nu, count, hp_d)
    return bias_step(params, counts, cfg_d), mu, nu, count, losses, counts


@partial(jax.jit, static_argnames=("cfg", "precision"))
def forward_jit(params, tokens, pos, ep, past, fault_code, cfg, precision="f32"):
    return forward(params, dict(cfg), tokens, pos, ep, past, precision, fault_code)


@partial(jax.jit, donate_argnums=(0,))
def extend_past(past, made, pos, ep, offset):
    """``past`` with what the layers ``made`` of tokens at ``pos``/``ep`` (B, T) written from column ``offset`` on."""
    put = lambda whole, part: jax.lax.dynamic_update_slice_in_dim(whole, part.astype(whole.dtype), offset, axis=1)  # noqa: E731
    return {"layers": jax.tree.map(put, past["layers"], made), "pos": put(past["pos"], pos), "ep": put(past["ep"], ep)}


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
