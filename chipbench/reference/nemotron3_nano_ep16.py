"""Plain float32 reference of the ``nemotron3_nano_ep16`` configuration: the forward pass of a decoder whose
layers are ONE part each (a Mamba-2 state-space mixer, a grouped-query attention mixer, or a sparse feed-forward
of ``relu^2`` experts), the masked PPO loss, its gradients and AdamW, in straightforward ``jax.numpy``.  Nothing
is imported from the program; only its parameter names are shared.

The equations, per layer on rows ``x`` of width H, ``u = rms(x; norm)`` and ``x <- x + part(u)``, every projection
without bias (NVIDIA-Nemotron-3-Nano-30B-A3B, ``model_type`` ``nemotron_h``; the installed ``transformers`` 4.57.6
has no ``models/nemotron_h``; read on this machine were the mixer's parents ``models/bamba/modeling_bamba.py``
``BambaMixer.torch_forward``, ``models/zamba2/modeling_zamba2.py`` ``Zamba2RMSNormGated``, ``models/mamba2``
``_init_weights``, and the router's, ``models/deepseek_v3/modeling_deepseek_v3.py``):

* Mamba-2 mixer (``mamba2``): ``[z, xBC, dt] = u W_in`` (inner, inner + 2 x groups x state, heads columns, in that
  order); ``xBC'_t = silu(b_c + sum_j conv_w[K - 1 - j] * xBC_{t - j})`` over ``j = 0 .. K - 1`` (depthwise, causal);
  ``[x', B, C] = split(xBC')``, ``x'`` as heads of ``head_dim``, ``B`` and ``C`` as groups of ``state``, head ``h``
  reading group ``h // (heads / groups)``; ``D_t = softplus(dt + dt_bias)``, ``a_t = exp(D_t * A)``, ``A = -exp(A_log)``;
  per head the state ``S`` (head_dim x state): **``S_t = a_t S_{t-1} + D_t x'_t (outer) B_t``**, ``S = 0`` before an
  episode's first token; ``y_t = S_t C_t + Dskip * x'_t``; ``g = y * silu(z)`` RMS-normed in ``groups`` groups of
  channels with a weight over all of them (the gate first, then the norm); ``g W_out``.  Written here as the
  SEQUENTIAL recurrence, a ``lax.scan`` over tokens that carries the state and the last ``K - 1`` ``xBC`` columns.
* attention mixer (``full_attention``): ``q, k, v = u Wq, u Wk, u Wv``; causal softmax over the whole episode; ``o Wo``.
  No rotary positions, no per-head norm, no gate.
* sparse feed-forward (``moe``): ``s = sigmoid(u W_r)`` over all experts, the ``k`` experts with the largest
  ``s + bias`` (the bias takes no gradient), weights ``route_scale * s_e / (sum of the selected s + 1e-20)``, each
  expert ``relu(u W1)^2 W2``; one shared expert of the same form (its own width), added for every token.

Departures from the published description, each also in ``chipbench/configs/nemotron3_nano_ep16.json``:

* the attention layer without rotary positions, the split order of ``W_in`` and the gated norm's order are the
  parents' code and ISSUE 36's writer's recollection of ``NemotronHAttention`` (the installed ``transformers`` has no
  ``nemotron_h``); ``time_step_limit`` is taken as unbounded (no clamp of ``D_t``);
* only the experts ``experts_held`` are computed (this chip's share; what the others would add is left out);
* the position is the position in the episode, a token neither attends to nor convolves over another episode's
  tokens, and the state before an episode's first token is nought (the model's own code has one sequence a row);
* a value head beside the language head (PPO's critic; the model has none).

What is plain here and is not in the program: no chunks (one token at a time; the only blocks are those needed to
fit: the scan is recomputed 16 tokens at a time in the backward pass, attention runs one env and one block of queries
at a time, and a layer is recomputed), no cache (every token finds the keys of its whole episode so far by episode
number and position among everything the env has seen), no grouped product (a loop over the held experts with a
dense mask), no fused phases.

It is teacher-forced: it takes the tokens the program sampled.  What the past gives a segment is data, not a
function of the parameters, as the program's carry is: for an attention layer the keys and values of every column
the env has seen, for a Mamba-2 layer the state and the last ``K - 1`` ``xBC`` columns at the segment's start (made
under the parameters that made them).  ``forward`` takes those and returns what it made of the segment: the keys and
values of its tokens, the state and the columns after its last real token.

``precision="fp8"`` rounds every matmul operand, and ``x'``, ``B`` and ``C`` where the recurrence multiplies them, to
e4m3 (the control: the precision below the configuration's bf16-mixed).  ``fault`` plants one of: ``ssm_reset`` (the
state is not cut at an episode's start), ``ssm_prefix`` (a segment starts from nought where the past's state
belongs), ``one_bc_group`` (every head reads group 0's ``B`` and ``C``), ``no_shared`` (the shared expert left out),
``half_batch`` (half of every minibatch left out).  The three state faults are asked for by the traced
``fault_code`` (1, 2, 3), so that none costs a compile of its own.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

MAMBA, MOE = "mamba2", "moe"
QUERY_BLOCK = 256
SCAN_BLOCK = 16  # tokens of the recurrence recomputed at a time in the backward pass
FAULT_CODES = {"ssm_reset": 1, "ssm_prefix": 2, "one_bc_group": 3}
ROUTE_EPS = 1e-20


def q8(x, precision: str):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32) if precision == "fp8" else x


def mm(a, b, precision: str):
    return jnp.matmul(q8(a, precision), q8(b, precision))


def rms(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def relu2(w, x, precision: str):
    return mm(jnp.square(jax.nn.relu(mm(x, w["w1"], precision))), w["w2"], precision)


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


def recurrence(layer, xbc, dt, pos, real, state, window, cfg, precision: str, fault_code):
    """The Mamba-2 layer's convolution and state, one token at a time: xbc (B, T, C) and dt (B, T, heads) as
    ``W_in`` gave them, pos (B, T), real (B, T) (a token that is not real leaves the state and the columns as
    it found them), state (B, heads, head_dim, state), window (B, K - 1, C) oldest first.
    -> y (B, T, heads, head_dim), the state and the columns after the last real token."""
    heads, P, G, N = cfg["ssm_heads"], cfg["ssm_head_dim"], cfg["ssm_groups"], cfg["ssm_state_size"]
    K, inner = cfg["ssm_conv_kernel"], cfg["ssm_heads"] * cfg["ssm_head_dim"]
    A = -jnp.exp(layer["A_log"])
    back = K - 1 - jnp.arange(K)  # column k of [window, token] lies `back[k]` tokens before the token

    def per_head(m):  # (B, G * N) -> (B, heads, N): head h reads group h // (heads / G)
        m = m.reshape(m.shape[0], G, N)
        m = jnp.where(fault_code == 3, m[:, :1], m)
        return jnp.repeat(m, heads // G, axis=1)

    def token(carry, xs):
        S, cols = carry
        xbc_t, dt_t, pos_t, real_t = xs
        cols = jnp.concatenate([cols, xbc_t[:, None]], axis=1)  # (B, K, C)
        live = pos_t[:, None] >= back[None]  # a tap that reaches before the episode's start reads nought
        u = jax.nn.silu(layer["conv_b"] + jnp.sum(jnp.where(live[..., None], layer["conv_w"][None] * cols, 0.0), axis=1))
        x = q8(u[:, :inner].reshape(-1, heads, P), precision)
        b, c = q8(per_head(u[:, inner:inner + G * N]), precision), q8(per_head(u[:, inner + G * N:]), precision)
        step = jax.nn.softplus(dt_t + layer["dt_bias"])  # (B, heads)
        before = jnp.where(((pos_t == 0) & (fault_code != 1))[:, None, None, None], 0.0, S)
        after = jnp.exp(step * A)[..., None, None] * before + (step[..., None] * x)[..., None] * b[:, :, None, :]
        y = jnp.einsum("bhpn,bhn->bhp", after, c) + layer["D"][:, None] * x
        keep = real_t[:, None, None]
        return (jnp.where(keep[..., None], after, S), jnp.where(keep, cols[:, 1:], cols[:, :-1])), y

    T = xbc.shape[1]
    blk = math.gcd(T, SCAN_BLOCK)
    blocks = lambda z: jnp.moveaxis(z, 1, 0).reshape((T // blk, blk) + z.shape[:1] + z.shape[2:])  # noqa: E731
    inner_scan = jax.checkpoint(lambda carry, xs: jax.lax.scan(token, carry, xs))
    state = jnp.where(fault_code == 2, jnp.zeros_like(state), state)
    (state, window), y = jax.lax.scan(inner_scan, (state, window), (blocks(xbc), blocks(dt), blocks(pos), blocks(real)))
    return jnp.moveaxis(y.reshape((T,) + y.shape[2:]), 0, 1), state, window


def experts_part(moe, m, cfg, precision: str, fault: Optional[str] = None):
    """Sigmoid router over all experts, the k largest of score + bias, weights scale * s / (sum s + 1e-20);
    the held experts' part, each held expert over every token under a dense mask, and the shared expert."""
    s = jax.nn.sigmoid(mm(m, moe["router"], precision))
    _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(moe["router_bias"]), cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = cfg["route_scale"] * w / (w.sum(axis=-1, keepdims=True) + ROUTE_EPS)
    counts = jnp.sum(jax.nn.one_hot(chosen, cfg["num_experts"], dtype=jnp.int32), axis=(0, 1))
    first, held = cfg["experts_held"]

    def one_expert(out, xs):  # every token through this expert, weighted by what the router gave it (mostly nought)
        e, one = xs
        weight = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=-1)
        return out + weight[:, None] * relu2(one, m, precision), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(m), (jnp.arange(held), moe["experts"]))
    if "shared" in moe and fault != "no_shared":
        out = out + relu2(moe["shared"], m, precision)
    return out, counts


def layer_forward(layer, x, pos, ep, past, past_pos, past_ep, fault_code, cfg, kind, precision, fault):
    """One layer over (B, T, H); ``past`` is this layer's constants: keys and values (B, P, KV, D) of an attention
    layer, the state and the last columns of a Mamba-2 layer, nothing of a feed-forward.  -> x', counts, what the
    layer made of the segment (the same tuple as ``past``)."""
    eps = cfg["rms_norm_eps"]
    B, T, H = x.shape
    if kind == MOE:
        f, counts = experts_part(layer["moe"], rms(x, layer["norm_pre_mlp"], eps).reshape(B * T, H), cfg, precision, fault)
        return x + f.reshape(B, T, H), counts, ()
    a = rms(x, layer["norm_in"], eps)
    if kind == MAMBA:
        inner, G = cfg["ssm_heads"] * cfg["ssm_head_dim"], cfg["ssm_groups"]
        conv_dim = inner + 2 * G * cfg["ssm_state_size"]
        proj = mm(a, layer["w_in"], precision)
        z, xbc, dt = proj[..., :inner], proj[..., inner:inner + conv_dim], proj[..., inner + conv_dim:]
        y, state, window = recurrence(layer, xbc, dt, pos, ep >= 0, past[0], past[1], cfg, precision, fault_code)
        g = (y.reshape(B, T, inner) * jax.nn.silu(z)).reshape(B, T, G, inner // G)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
        return x + mm(g.reshape(B, T, inner) * layer["norm_gate"], layer["w_out"], precision), None, (state, window)
    D, KV = cfg["head_dim"], cfg["num_key_value_heads"]
    G = cfg["num_attention_heads"] // KV
    q = mm(a, layer["wq"], precision).reshape(B, T, KV, G, D)
    k = mm(a, layer["wk"], precision).reshape(B, T, KV, D)
    v = mm(a, layer["wv"], precision).reshape(B, T, KV, D)
    pos_k, ep_k = jnp.concatenate([past_pos, pos], 1), jnp.concatenate([past_ep, ep], 1)
    keys, values = jnp.concatenate([past[0], k], 1), jnp.concatenate([past[1], v], 1)
    one_env = jax.checkpoint(lambda z: attention(*z, precision))
    o = jax.lax.map(one_env, (q, keys, values, pos, ep, pos_k, ep_k)).reshape(B, T, -1)
    return x + mm(o, layer["wo"], precision), None, (k, v)


def forward(params, cfg: Dict[str, Any], tokens, pos, ep, past, precision: str = "f32", fault_code=0, fault: Optional[str] = None):
    """tokens, pos, ep (B, T) on ``past`` = {"layers": [per layer (k, v) each (B, P, KV, D), or (state, window), or ()],
    "pos", "ep" (B, P)} -> logits (B, T, V), values (B, T), router counts (expert layers, E), what each layer made of
    these tokens (as ``past["layers"]``).  ``ep`` numbers an env's episodes (-1: padding, seen by no real query and
    passed over by the recurrence)."""
    x = params["embed"][tokens]
    counts, made = [], []
    for i, kind in enumerate(cfg["layer_types"]):
        run = jax.checkpoint(layer_forward, static_argnums=(8, 9, 10, 11))
        x, c, m = run(params[f"layer_{i}"], x, pos, ep, past["layers"][i], past["pos"], past["ep"],
                      jnp.asarray(fault_code, jnp.int32), _Static(cfg), kind, precision, fault)
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
    conv_dim = cfg["ssm_heads"] * cfg["ssm_head_dim"] + 2 * cfg["ssm_groups"] * cfg["ssm_state_size"]
    state = (batch, cfg["ssm_heads"], cfg["ssm_head_dim"], cfg["ssm_state_size"])

    def of(kind):
        if kind == MOE:
            return ()
        if kind == MAMBA:
            return (jnp.zeros(state), jnp.zeros((batch, cfg["ssm_conv_kernel"] - 1, conv_dim)))
        return (jnp.zeros(kv), jnp.zeros(kv))

    return {"layers": [of(kind) for kind in cfg["layer_types"]],
            "pos": jnp.zeros((batch, length), jnp.int32), "ep": jnp.full((batch, length), -1, jnp.int32)}


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


def ppo_loss(params, cfg, hp, batch, past, precision, fault_code=0, fault=None):
    """Masked PPO loss of one minibatch (arrays (B, T)): clipped surrogate, plain squared value error,
    entropy, each a mean over the steps whose mask is 1."""
    logits, values, counts, _ = forward(params, cfg, batch["tokens"], batch["pos"], batch["ep"], past, precision, fault_code, fault)
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


def bias_step(params, counts, cfg):
    """b += coeff * sign(mean load - load_e) over the router's counts of this update, every expert layer."""
    out = dict(params)
    moe_layers = [i for i, kind in enumerate(cfg["layer_types"]) if kind == MOE]
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
        params, cfg_d, hp_d, batch, past, precision, fault_code, None if fault == "half_batch" else fault
    )
    params, mu, nu, count = adamw_step(params, grads, mu, nu, count, hp_d)
    return bias_step(params, counts, cfg_d), mu, nu, count, losses, counts


@partial(jax.jit, static_argnames=("cfg", "precision", "fault"))
def forward_jit(params, tokens, pos, ep, past, fault_code, cfg, precision="f32", fault: Optional[str] = None):
    return forward(params, dict(cfg), tokens, pos, ep, past, precision, fault_code, None if fault == "half_batch" else fault)


@partial(jax.jit, static_argnames=("kinds",), donate_argnums=(0,))
def extend_past(past, made, pos, ep, offset, kinds):
    """``past`` with what the layers (of ``kinds``) ``made`` of tokens at ``pos``/``ep`` (B, T): an attention layer's
    keys and values written from column ``offset`` on, a Mamba-2 layer's state and columns in the place of the old ones."""
    put = lambda whole, part: jax.lax.dynamic_update_slice_in_dim(whole, part.astype(whole.dtype), offset, axis=1)  # noqa: E731
    layers = [new if kind in (MAMBA, MOE) else tuple(put(w, p) for w, p in zip(old, new))
              for kind, old, new in zip(kinds, past["layers"], made)]
    return {"layers": layers, "pos": put(past["pos"], pos), "ep": put(past["ep"], ep)}


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
