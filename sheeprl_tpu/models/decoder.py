"""A decoder sequence block for token-level policies: pure functions over a parameter dict.

What the other networks of ``models/models.py`` do not have: RMS norm, rotary
positions, three kinds of sequence mixer (grouped-query attention with a
sliding window, the same over the whole episode, and a gated short convolution,
``layer_types``), a gated feed-forward, and a sparse-expert layer that is told
which experts it holds (``experts_held``), routes over all of them and computes
its own experts' part of the result.  What stands around a mixer (the norms, the
output gate, which layers take rotary positions, the embedding multiplier, the
shared expert) is data of :class:`DecoderConfig`, stated by a yaml of
``configs/algo/decoder``; its defaults are the ``afmoe`` family's (Arcee
Trinity), and ``lfm2_24b.yaml`` states the ``lfm2_moe`` family's (LiquidAI).
``howto/ppo_tokens.md`` and the files of ``chipbench/configs`` say which
equations a published ``config.json`` settles and which are assumed.

Two entry points serve the recurrent PPO loop, and share every projection:

* :func:`step` runs ONE token per env through the caches of the carry;
* :func:`segment` runs ``T`` tokens per env whose keys are the carry's cached
  prefix (constants) followed by the segment's own, in query blocks, so that no
  ``T x (prefix + T) x heads`` float32 array is ever whole.  With
  ``extend=True`` it also returns the carry with the segment written into it
  (prefill).

The carry is a pytree, per env: for every attention layer a buffer of keys and
of values (a ring of ``sliding_window`` positions for a sliding layer,
``max_len`` for a full one; slot = position mod size; a slot is one row of
``num_key_value_heads * head_dim`` lanes, the heads side by side, so that a row
is whole lanes whatever the head width), for every conv layer the gated inputs
of the last ``conv_L_cache - 1`` tokens (oldest first), and the position of the
next token.  A reset only zeroes the position: which slots hold
keys of the running episode, and which of a convolution's taps reach a token of
it (tap ``j`` of the token at position ``p`` iff ``p - j >= 0``), follows from
the position alone, so nothing has to be cleared.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from sheeprl_tpu.ops import decode_attention

Params = Dict[str, Any]
Carry = Dict[str, Any]

SLIDING = "sliding_attention"
FULL = "full_attention"  # attends to the whole episode
CONV = "conv"  # a gated short convolution: no keys, a window of gated inputs
Q_BLOCK = 64  # queries per attention block of a segment: 64 x (prefix + T) x 32 heads of float32 scores at a time


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int  # rows of the embedding and of the head held here (the slice)
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int  # the dense layers' feed-forward width
    moe_intermediate_size: int  # one expert's width (the shared expert's too)
    num_experts: int  # the router's outputs: ALL experts of the layer
    num_experts_per_tok: int
    experts_held: Tuple[int, int]  # (first, count) of the experts whose weights live here
    layer_types: Tuple[str, ...]
    num_dense_layers: int  # leading layers with a dense feed-forward
    max_len: int  # positions a full-attention layer caches: the longest episode
    sliding_window: int = 0  # positions a sliding layer sees; a model without such a layer has none
    conv_L_cache: int = 3  # taps of a conv layer
    num_shared_experts: int = 1  # 0: no shared expert beside the routed ones
    post_norms: bool = True  # a norm on what the mixer and the feed-forward give, before the residual add
    attn_output_gate: bool = True  # o * sigmoid(a Wg) before the output projection
    rope_layers: Tuple[str, ...] = (SLIDING,)  # the kinds of attention layer that take rotary positions
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    route_scale: float = 1.0
    route_norm: bool = True
    route_eps: float = 1e-20  # beside the sum of the selected scores
    mup_enabled: bool = True
    load_balance_coeff: float = 1e-3
    init_std: float = 0.02  # of the seeded matrices; about 1 / sqrt(hidden_size) keeps a tiny model's signal like a wide one's

    @staticmethod
    def from_dict(d: Dict[str, Any], vocab_size: int, max_len: int) -> "DecoderConfig":
        fields = {f.name for f in dataclasses.fields(DecoderConfig)}
        kw = {k: v for k, v in d.items() if k in fields}
        kw["layer_types"] = tuple(kw["layer_types"])
        kw["rope_layers"] = tuple(kw.get("rope_layers", (SLIDING,)))
        unknown = set(kw["layer_types"]) - {SLIDING, FULL, CONV}
        if unknown or (SLIDING in kw["layer_types"] and not kw.get("sliding_window")):
            raise ValueError(f"layer_types {kw['layer_types']}: unknown kinds {sorted(unknown)}, or a sliding layer without sliding_window")
        kw["experts_held"] = tuple(int(x) for x in kw["experts_held"])
        return DecoderConfig(**{**kw, "vocab_size": int(vocab_size), "max_len": int(max_len)})

    @property
    def groups(self) -> int:
        return self.num_attention_heads // self.num_key_value_heads

    def cache_len(self, layer: int) -> int:
        """Positions attention layer ``layer`` caches."""
        return self.sliding_window if self.layer_types[layer] == SLIDING else self.max_len

    def layers_of(self, *kinds: str) -> Tuple[int, ...]:
        return tuple(i for i, kind in enumerate(self.layer_types) if kind in kinds)

    def carry_slot(self, layer: int) -> int:
        """Where the carry keeps layer ``layer``'s state, among those of its kind (attention, or conv)."""
        kinds = (CONV,) if self.layer_types[layer] == CONV else (SLIDING, FULL)
        return self.layers_of(*kinds).index(layer)

    def moe_layers(self) -> Tuple[int, ...]:
        return tuple(i for i in range(len(self.layer_types)) if i >= self.num_dense_layers)


# ----------------------------------------------------------------------------
# parameters and carry
# ----------------------------------------------------------------------------

def init_params(dc: DecoderConfig, key: jax.Array, std: Optional[float] = None) -> Params:
    """Seeded float32 parameters: normal(0, ``std``) matrices (``init_std`` unless given), unit norms, zero selection bias."""
    std = dc.init_std if std is None else std
    H, D = dc.hidden_size, dc.head_dim
    Q, KV = dc.num_attention_heads * D, dc.num_key_value_heads * D
    keys = iter(jax.random.split(key, 16 * len(dc.layer_types) + 8))

    def mat(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32) * std

    def ffn(width, lead=()):
        return {"w1": mat(*lead, H, width), "w3": mat(*lead, H, width), "w2": mat(*lead, width, H)}

    params: Params = {"embed": mat(dc.vocab_size, H)}
    for i, kind in enumerate(dc.layer_types):
        layer = {"norm_in": jnp.ones((H,)), "norm_pre_mlp": jnp.ones((H,))}
        if dc.post_norms:
            layer.update(norm_post_attn=jnp.ones((H,)), norm_post_mlp=jnp.ones((H,)))
        if kind == CONV:  # conv_w[k] weighs the gated input `conv_L_cache - 1 - k` tokens back
            layer.update(w_in=mat(H, 3 * H), conv_w=mat(dc.conv_L_cache, H), w_out=mat(H, H))
        else:
            layer.update(q_norm=jnp.ones((D,)), k_norm=jnp.ones((D,)), wq=mat(H, Q), wk=mat(H, KV), wv=mat(H, KV))
            if dc.attn_output_gate:
                layer["wg"] = mat(H, Q)
            layer["wo"] = mat(Q, H)
        if i < dc.num_dense_layers:
            layer["mlp"] = ffn(dc.intermediate_size)
        else:
            layer["moe"] = {"router": mat(H, dc.num_experts), "router_bias": jnp.zeros((dc.num_experts,))}
            if dc.num_shared_experts:
                layer["moe"]["shared"] = ffn(dc.moe_intermediate_size * dc.num_shared_experts)
            layer["moe"]["experts"] = ffn(dc.moe_intermediate_size, lead=(dc.experts_held[1],))
        params[f"layer_{i}"] = layer
    params.update(norm_out=jnp.ones((H,)), head=mat(H, dc.vocab_size), value_head=mat(H, 1))
    return params


def init_carry(dc: DecoderConfig, batch: int, dtype: Any = jnp.bfloat16) -> Carry:
    """``k`` and ``v`` hold one buffer per attention layer and ``conv`` one window per conv layer, each in the
    layers' order; a kind the model lacks has no entry."""
    shape = lambda i: (batch, dc.cache_len(i), dc.num_key_value_heads * dc.head_dim)  # noqa: E731
    attn, conv = dc.layers_of(SLIDING, FULL), dc.layers_of(CONV)
    carry = {
        "k": [jnp.zeros(shape(i), dtype) for i in attn],
        "v": [jnp.zeros(shape(i), dtype) for i in attn],
        "pos": jnp.zeros((batch,), jnp.int32),
    }
    if conv:
        carry["conv"] = [jnp.zeros((batch, dc.conv_L_cache - 1, dc.hidden_size), dtype) for _ in conv]
    return carry


def carry_bytes(dc: DecoderConfig, dtype: Any = jnp.bfloat16) -> Dict[str, int]:
    """Bytes of recurrent carry one env holds, by kind of layer (and its position)."""
    shapes = jax.eval_shape(lambda: init_carry(dc, 1, dtype))
    size = lambda x: int(math.prod(x.shape)) * x.dtype.itemsize  # noqa: E731
    out = {"pos": size(shapes["pos"])}
    for i, k, v in zip(dc.layers_of(SLIDING, FULL), shapes["k"], shapes["v"]):
        out[dc.layer_types[i]] = out.get(dc.layer_types[i], 0) + size(k) + size(v)
    if "conv" in shapes:
        out[CONV] = sum(size(z) for z in shapes["conv"])
    return out


def update_router_bias(params: Params, load: jax.Array, dc: DecoderConfig) -> Params:
    """The selection bias after one update: ``b += coeff * sign(mean load - load_e)`` over the router's
    counts of that update (``load``: one row of ``num_experts`` counts per expert layer)."""
    out = dict(params)
    for row, i in enumerate(dc.moe_layers()):
        counts = load[row].astype(jnp.float32)
        layer = dict(out[f"layer_{i}"])
        moe = dict(layer["moe"])
        moe["router_bias"] = moe["router_bias"] + dc.load_balance_coeff * jnp.sign(counts.mean() - counts)
        layer["moe"] = moe
        out[f"layer_{i}"] = layer
    return out


# ----------------------------------------------------------------------------
# pieces
# ----------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotary positions on the last axis (halves rotated against each other); ``pos`` has ``x``'s
    leading axes up to the two of heads."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[..., None] * inv
    extra = x.ndim - ang.ndim
    ang = ang.reshape(ang.shape[:-1] + (1,) * extra + ang.shape[-1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : d // 2], x32[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def segment_positions(is_first: jax.Array, pos0: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``(T, B)`` position in the episode of every token of a segment and the number of resets at or
    before it (0: still the episode the carry's caches belong to).  ``is_first`` resets before the token."""
    first = is_first.astype(jnp.int32)
    T = first.shape[0]
    t = jnp.arange(T, dtype=jnp.int32)[:, None]
    last_reset = jax.lax.cummax(jnp.where(first > 0, t, -1), axis=0)
    pos = jnp.where(last_reset >= 0, t - last_reset, pos0[None].astype(jnp.int32) + t)
    return pos, jnp.cumsum(first, axis=0)


def slot_positions(last: jax.Array, size: int) -> jax.Array:
    """``(B, size)``: the position whose key each slot holds when ``last`` (``(B,)``, -1 for none) was the
    newest position written; negative where the slot holds nothing of the running episode."""
    s = jnp.arange(size, dtype=jnp.int32)[None]
    last = last.astype(jnp.int32)[:, None]
    return last - jnp.mod(last - s, size)


def _attend(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array) -> jax.Array:
    """q (B, T, KV, G, D), k/v (B, S, KV, D), mask (B, T, S) -> (B, T, KV*G*D); softmax in float32."""
    scores = jnp.einsum("btkgd,bskd->bkgts", q, k, preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(q.shape[-1])
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", probs.astype(v.dtype), v)
    return out.reshape(out.shape[:2] + (-1,))


def _per_head(cache: jax.Array, dc: DecoderConfig) -> jax.Array:
    """The carry's rows (B, S, KV * D) as ``_attend`` reads keys and values: (B, S, KV, D)."""
    return cache.reshape(cache.shape[:2] + (dc.num_key_value_heads, dc.head_dim))


def _ffn(w: Params, x: jax.Array) -> jax.Array:
    dt = x.dtype
    return (jax.nn.silu(x @ w["w1"].astype(dt)) * (x @ w["w3"].astype(dt))) @ w["w2"].astype(dt)


def route(moe: Params, m: jax.Array, dc: DecoderConfig) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sigmoid scores over ALL experts, the ``k`` largest of ``score + bias`` (the bias has no gradient),
    weights ``route_scale * s / (sum s + route_eps)``.  Returns (experts (N, k), weights (N, k) float32, counts (E,))."""
    s = jax.nn.sigmoid(jnp.matmul(  # few columns: cheap in full precision, and a coarser product reorders near ties
        m.astype(jnp.float32), moe["router"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST))
    _, experts = jax.lax.top_k(s + jax.lax.stop_gradient(moe["router_bias"].astype(jnp.float32)), dc.num_experts_per_tok)
    w = jnp.take_along_axis(s, experts, axis=-1)
    if dc.route_norm:
        w = w / (w.sum(axis=-1, keepdims=True) + dc.route_eps)
    counts = jnp.zeros((dc.num_experts,), jnp.int32).at[experts.reshape(-1)].add(1)
    return experts, w * dc.route_scale, counts


def held_experts(w: Params, m: jax.Array, experts: jax.Array, weights: jax.Array, dc: DecoderConfig) -> jax.Array:
    """The held experts' part of the layer's result, dropless: the ``N * k`` (token, expert) pairs are sorted by
    expert, those of experts held elsewhere last, and the held experts run as one grouped product over all of
    them.  No capacity and no second program: whatever share of the pairs is routed here has its rows."""
    first, held = dc.experts_held
    N, k = experts.shape
    local = experts.reshape(-1) - first
    local = jnp.where((local >= 0) & (local < held), local, held)  # held elsewhere: after every group
    order = jnp.argsort(local, stable=True)
    sizes = jnp.zeros((held + 1,), jnp.int32).at[local].add(1)
    token = order // k
    ours = (local[order] < held)[:, None]
    # Every row has to lie in a group: a grouped product writes the rows of its groups and nothing else (on the
    # TPU the others keep whatever the buffer held, NaNs among it, in the backward pass too).  The rows of
    # experts held elsewhere come after the last group, so that group takes them in, as rows of nought: they
    # give nought, take nought back, and add nought to the last expert's gradient.
    groups = sizes[:held].at[held - 1].add(sizes[held])
    x = jnp.where(ours, jnp.take(m, token, axis=0), 0)
    dt = x.dtype
    h = jax.nn.silu(jax.lax.ragged_dot(x, w["w1"].astype(dt), groups)) * jax.lax.ragged_dot(x, w["w3"].astype(dt), groups)
    y = jax.lax.ragged_dot(h, w["w2"].astype(dt), groups)
    y = jnp.where(ours, y.astype(jnp.float32) * weights.reshape(-1)[order][:, None], 0.0)
    return jnp.zeros((N, m.shape[-1]), jnp.float32).at[token].add(y).astype(dt)


def _mlp(layer: Params, x: jax.Array, dc: DecoderConfig) -> Tuple[jax.Array, Optional[jax.Array]]:
    """``x + [norm_post_mlp](f(norm_pre_mlp(x)))`` on (N, H) rows; the router's counts where the layer has experts."""
    m = rms_norm(x, layer["norm_pre_mlp"], dc.rms_norm_eps)
    counts = None
    if "mlp" in layer:
        f = _ffn(layer["mlp"], m)
    else:
        moe = layer["moe"]
        with jax.named_scope("policy.moe.route"):
            experts, weights, counts = route(moe, m, dc)
        shared = None
        if dc.num_shared_experts:
            with jax.named_scope("policy.moe.shared"):
                shared = _ffn(moe["shared"], m)
        with jax.named_scope("policy.moe.experts"):
            f = held_experts(moe["experts"], m, experts, weights, dc)
            f = f if shared is None else shared + f
    return x + (rms_norm(f, layer["norm_post_mlp"], dc.rms_norm_eps) if dc.post_norms else f), counts


def _qkv(layer: Params, a: jax.Array, pos: jax.Array, rotary: bool, dc: DecoderConfig):
    """Projections of normed rows ``a`` (..., H) at positions ``pos`` (...): q (..., KV, G, D), k, v (..., KV, D)."""
    dt, D, KV = a.dtype, dc.head_dim, dc.num_key_value_heads
    lead = a.shape[:-1]
    q = (a @ layer["wq"].astype(dt)).reshape(lead + (KV, dc.groups, D))
    k = (a @ layer["wk"].astype(dt)).reshape(lead + (KV, D))
    v = (a @ layer["wv"].astype(dt)).reshape(lead + (KV, D))
    q = rms_norm(q, layer["q_norm"], dc.rms_norm_eps)
    k = rms_norm(k, layer["k_norm"], dc.rms_norm_eps)
    if rotary:
        q, k = rope(q, pos, dc.rope_theta), rope(k, pos, dc.rope_theta)
    return q, k, v


def _after_mixer(layer: Params, x: jax.Array, y: jax.Array, dc: DecoderConfig) -> jax.Array:
    return x + (rms_norm(y, layer["norm_post_attn"], dc.rms_norm_eps) if dc.post_norms else y)


def _after_attention(layer: Params, x: jax.Array, a: jax.Array, o: jax.Array, dc: DecoderConfig) -> jax.Array:
    dt = x.dtype
    if dc.attn_output_gate:
        o = o * jax.nn.sigmoid(a @ layer["wg"].astype(dt))
    return _after_mixer(layer, x, o @ layer["wo"].astype(dt), dc)


def _conv_gates(layer: Params, a: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Normed rows ``a`` (..., H) -> the gated input ``B * u`` the convolution reads, and the gate ``C`` on what it gives."""
    b, c, u = jnp.split(a @ layer["w_in"].astype(a.dtype), 3, axis=-1)
    return b * u, c


def _conv_taps(layer: Params, window: jax.Array, pos: jax.Array, T: int) -> jax.Array:
    """The causal depthwise convolution as shifted multiply-adds.  ``window`` (B, L - 1 + T, H): the gated
    inputs of the ``L - 1`` tokens before the ``T`` tokens whose positions are ``pos`` (B, T), then theirs.
    Tap ``j`` reads the token ``j`` back, and is cut where that token lies before the episode's start."""
    last = window.shape[1] - T  # L - 1
    w = layer["conv_w"].astype(window.dtype)
    out = w[last] * window[:, last:]
    for j in range(1, last + 1):
        out = out + jnp.where((pos >= j)[..., None], w[last - j] * window[:, last - j: last - j + T], 0)
    return out


def _heads(params: Params, x: jax.Array, dc: DecoderConfig) -> Tuple[jax.Array, jax.Array]:
    with jax.named_scope("policy.head"):
        h = rms_norm(x, params["norm_out"], dc.rms_norm_eps)
        logits = (h @ params["head"].astype(h.dtype)).astype(jnp.float32)
        value = (h @ params["value_head"].astype(h.dtype)).astype(jnp.float32)
    return logits, value


def _embed(params: Params, tokens: jax.Array, dc: DecoderConfig, dtype: Any) -> jax.Array:
    x = jnp.take(params["embed"], tokens.astype(jnp.int32), axis=0).astype(dtype)
    return x * jnp.asarray(math.sqrt(dc.hidden_size), dtype) if dc.mup_enabled else x


def _next_carry(carry: Carry, new: Carry, pos: jax.Array) -> Carry:
    """``carry``'s own kinds of state from ``new``, at ``pos``."""
    return {**{k: v for k, v in new.items() if k in carry}, "pos": pos}


def _scope(kind: str) -> str:
    return {SLIDING: "policy.attn.window", FULL: "policy.attn.full", CONV: "policy.conv"}[kind]


# ----------------------------------------------------------------------------
# one token through the caches
# ----------------------------------------------------------------------------

def step(params: Params, dc: DecoderConfig, carry: Carry, tokens: jax.Array, is_first: jax.Array, dtype: Any):
    """``tokens`` (B,), ``is_first`` (B,) -> (carry', logits (B, V), value (B, 1)).  A reset empties the
    env's caches (its position goes to nought) before the token is read."""
    pos = jnp.where(is_first > 0, 0, carry["pos"]).astype(jnp.int32)
    x = _embed(params, tokens, dc, dtype)
    new: Carry = {"k": [], "v": [], "conv": []}
    for i, kind in enumerate(dc.layer_types):
        layer = params[f"layer_{i}"]
        with jax.named_scope(_scope(kind)):
            a = rms_norm(x, layer["norm_in"], dc.rms_norm_eps)
            if kind == CONV:
                z, gate = _conv_gates(layer, a)
                state = carry["conv"][dc.carry_slot(i)]
                window = jnp.concatenate([state.astype(dtype), z[:, None]], axis=1)
                y = gate * _conv_taps(layer, window, pos[:, None], 1)[:, 0]
                x = _after_mixer(layer, x, y @ layer["w_out"].astype(dtype), dc)
                new["conv"].append(window[:, 1:].astype(state.dtype))  # the window moves on by one token
            else:
                n, size = dc.carry_slot(i), dc.cache_len(i)
                q, k, v = _qkv(layer, a, pos, kind in dc.rope_layers, dc)
                slot = jnp.mod(pos, size)
                write = jax.vmap(lambda c, s, row: jax.lax.dynamic_update_slice(c, row.reshape(1, -1).astype(c.dtype), (s, 0)))
                ck, cv = write(carry["k"][n], slot, k), write(carry["v"][n], slot, v)
                if decode_attention.engages(size):  # a cache with blocks to skip is read as far as each env has written it
                    # a ring keeps the last `size` positions and nothing older: unwrapped, and in a full cache, slots [0, pos]
                    o = decode_attention.decode_attention(q, ck, cv, jnp.minimum(pos + 1, size)).astype(dtype)
                else:
                    mask = slot_positions(pos, size) >= 0
                    o = _attend(q[:, None], _per_head(ck, dc).astype(dtype), _per_head(cv, dc).astype(dtype), mask[:, None])[:, 0]
                x = _after_attention(layer, x, a, o, dc)
                new["k"].append(ck)
                new["v"].append(cv)
        x, _ = _mlp(layer, x, dc)
    logits, value = _heads(params, x, dc)
    return _next_carry(carry, new, pos + 1), logits, value


# ----------------------------------------------------------------------------
# a segment on a cached prefix
# ----------------------------------------------------------------------------

def _segment_layer(layer: Params, x, pos, seg, prefix_k, prefix_v, prefix_pos, dc: DecoderConfig, kind: str):
    """One attention layer over (B, T, H) rows.  Returns (x', router counts or None, k, v of the segment)."""
    B, T, H = x.shape
    dt = x.dtype
    sliding = kind == SLIDING
    with jax.named_scope(_scope(kind)):
        a = rms_norm(x, layer["norm_in"], dc.rms_norm_eps)
        q, k, v = _qkv(layer, a, pos, kind in dc.rope_layers, dc)
        keys = jnp.concatenate([prefix_k.astype(dt), k], axis=1)
        values = jnp.concatenate([prefix_v.astype(dt), v], axis=1)
        t = jnp.arange(T, dtype=jnp.int32)
        # the prefix: keys of the carry's episode, for queries before the segment's first reset
        on_prefix = (seg[:, :, None] == 0) & (prefix_pos[:, None, :] >= 0)
        own = (t[None, :, None] >= t[None, None, :]) & (seg[:, :, None] == seg[:, None, :])
        if sliding:
            on_prefix &= pos[:, :, None] - prefix_pos[:, None, :] < dc.sliding_window
            own &= (t[:, None] - t[None, :] < dc.sliding_window)[None]
        mask = jnp.concatenate([on_prefix, own], axis=2)
        qb = min(Q_BLOCK, T)
        if T % qb:
            raise ValueError(f"a segment of {T} tokens does not divide into query blocks of {qb}")
        blocks = lambda z: jnp.moveaxis(z.reshape((B, T // qb, qb) + z.shape[2:]), 1, 0)  # noqa: E731
        attend = jax.checkpoint(lambda qm: _attend(qm[0], keys, values, qm[1]))
        o = jax.lax.map(attend, (blocks(q), blocks(mask)))
        o = jnp.moveaxis(o, 0, 1).reshape(B, T, -1)
        x = _after_attention(layer, x, a, o, dc)
    y, counts = _mlp(layer, x.reshape(B * T, H), dc)
    return y.reshape(B, T, H), counts, k, v


def _segment_conv_layer(layer: Params, x, pos, state, dc: DecoderConfig):
    """One conv layer over (B, T, H) rows whose first taps read the carry's ``state`` (B, L - 1, H).
    Returns (x', router counts or None, the window: the carry's rows, then the segment's gated inputs)."""
    B, T, H = x.shape
    with jax.named_scope(_scope(CONV)):
        a = rms_norm(x, layer["norm_in"], dc.rms_norm_eps)
        z, gate = _conv_gates(layer, a)
        window = jnp.concatenate([state.astype(x.dtype), z], axis=1)
        y = gate * _conv_taps(layer, window, pos, T)
        x = _after_mixer(layer, x, y @ layer["w_out"].astype(x.dtype), dc)
    y, counts = _mlp(layer, x.reshape(B * T, H), dc)
    return y.reshape(B, T, H), counts, window


def segment(
    params: Params, dc: DecoderConfig, carry: Carry, tokens: jax.Array, is_first: jax.Array, dtype: Any,
    extend: bool = False, valid: Optional[jax.Array] = None,
):
    """``tokens``, ``is_first`` (T, B) on the prefix cached in ``carry`` (constants: nothing is
    differentiated through them) -> (logits (T, B, V), values (T, B, 1), router counts (expert layers,
    E)), and with ``extend`` the carry that holds the segment as well.  ``valid`` (B,), with ``extend``:
    only each env's first ``valid`` tokens are real (a ragged prefill); the others are not written."""
    carry = jax.lax.stop_gradient(carry)
    pos_tb, seg_tb = segment_positions(is_first, carry["pos"])
    pos, seg = pos_tb.T, seg_tb.T  # (B, T)
    x = _embed(params, tokens.T, dc, dtype)
    T = x.shape[1]
    if extend:  # real tokens an env
        n = jnp.full(pos.shape[:1], T, jnp.int32) if valid is None else valid.astype(jnp.int32)
    counts = []
    new: Carry = {"k": [], "v": [], "conv": []}
    for i, kind in enumerate(dc.layer_types):
        if kind == CONV:
            state = carry["conv"][dc.carry_slot(i)]
            run = jax.checkpoint(_segment_conv_layer, static_argnums=(4,))
            x, c, window = run(params[f"layer_{i}"], x, pos, state, dc)
            if extend:  # the gated inputs of the last L - 1 real tokens; the carry's own rows where there are fewer
                keep = jax.vmap(lambda rows, start: jax.lax.dynamic_slice_in_dim(rows, start, state.shape[1], axis=0))
                new["conv"].append(keep(window, n).astype(state.dtype))
        else:
            at, size = dc.carry_slot(i), dc.cache_len(i)
            prefix_pos = slot_positions(carry["pos"] - 1, size)
            run = jax.checkpoint(_segment_layer, static_argnums=(7, 8))
            prefix_k, prefix_v = _per_head(carry["k"][at], dc), _per_head(carry["v"][at], dc)
            x, c, k, v = run(params[f"layer_{i}"], x, pos, seg, prefix_k, prefix_v, prefix_pos, dc, kind)
            if extend:
                if T > size:
                    raise ValueError("a prefill segment longer than the window would write a slot twice")
                real = jnp.arange(T)[None] < n[:, None]
                slot = jnp.where(real, jnp.mod(pos, size), size)  # out of range: dropped
                put = jax.vmap(lambda cache, s, rows: cache.at[s].set(rows.reshape(T, -1).astype(cache.dtype), mode="drop"))
                new["k"].append(put(carry["k"][at], slot, k))
                new["v"].append(put(carry["v"][at], slot, v))
        if c is not None:
            counts.append(c)
    logits, values = _heads(params, x, dc)
    logits, values = jnp.moveaxis(logits, 0, 1), jnp.moveaxis(values, 0, 1)
    load = jnp.stack(counts) if counts else jnp.zeros((0, dc.num_experts), jnp.int32)
    if not extend:
        return logits, values, load
    last = jnp.take_along_axis(pos, jnp.maximum(n - 1, 0)[:, None], axis=1)[:, 0]
    new_pos = jnp.where(n > 0, last + 1, carry["pos"])
    return logits, values, load, _next_carry(carry, new, new_pos)
