"""A decoder sequence block for token-level policies: pure functions over a parameter dict.

What the other networks of ``models/models.py`` do not have: RMS norm, rotary
positions, five kinds of sequence mixer (grouped-query attention with a
sliding window, the same over the whole episode, the same over the keys a
learned indexer selects, a gated short convolution and a Mamba-2 state-space
layer, ``layer_types``), a feed-forward in two forms
(silu-gated with three matrices, ``relu^2`` with two), and a sparse-expert
layer that is told which experts it holds (``experts_held``), routes over all
of them and computes its own experts' part of the result.  What a layer is made
of (a mixer and a feed-forward, or, with ``mixer_ffn: False``, one of them alone:
a mixer, or the kind ``moe``) and what stands around a mixer (the norms, the
output gate, the per-head norms, which layers take rotary positions, the
embedding multiplier, the shared expert and its width) is data of
:class:`DecoderConfig`, stated by a yaml of ``configs/algo/decoder``; its
defaults are the ``afmoe`` family's (Arcee Trinity), ``lfm2_24b.yaml`` states
the ``lfm2_moe`` family's (LiquidAI), ``nemotron3_nano.yaml`` the
``nemotron_h`` family's (NVIDIA) and ``keye_vl2.yaml`` the ``KeyeVL2`` family's
language model (Kwai-Keye).  ``howto/ppo_tokens.md`` and the files of
``chipbench/configs`` say which equations a published ``config.json`` settles
and which are assumed.

Two entry points serve the recurrent PPO loop, and share every projection:

* :func:`step` runs ONE token per env through the caches of the carry;
* :func:`segment` runs ``T`` tokens per env whose keys are the carry's cached
  prefix (constants) followed by the segment's own, in query blocks, so that no
  ``T x (prefix + T) x heads`` float32 array is ever whole.  With
  ``extend=True`` it also returns the carry with the segment written into it
  (prefill).  A sparse layer's prefix of two blocks or more is read by the
  kernels of ``ops/segment_attention.py``, each env's only where its queries
  selected a key, and no per-head score over the prefix reaches HBM.

The carry is a pytree, per env: for every attention layer a buffer of keys and
of values (a ring of ``sliding_window`` positions for a sliding layer,
``max_len`` for a full one; slot = position mod size; a slot is one row of
``num_key_value_heads * head_dim`` lanes, the heads side by side, so that a row
is whole lanes whatever the head width; a sparse layer besides keeps one index key
of ``index_head_dim`` lanes a position), for every conv layer the gated inputs
of the last ``conv_L_cache - 1`` tokens (oldest first), for every Mamba-2 layer
its state (per head ``ssm_head_dim x ssm_state_size``, float32 whatever the
compute dtype: it accumulates over thousands of steps at decays near 1) and the
last ``ssm_conv_kernel - 1`` inputs of its convolution, and the position of the
next token.  A reset only zeroes the position: which slots hold
keys of the running episode, and which of a convolution's taps reach a token of
it (tap ``j`` of the token at position ``p`` iff ``p - j >= 0``), follows from
the position alone, so nothing has to be cleared.  A state-space state has no
positions, so it is the one thing a reset has to reach: the state before the
token at position 0 counts for nought, in ``step`` by the position and in
``segment`` inside a chunk of the scan, at a chunk's edge and at the segment's
first token (:func:`_ssm_scan`).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from sheeprl_tpu.ops import decode_attention, segment_attention

Params = Dict[str, Any]
Carry = Dict[str, Any]

SLIDING = "sliding_attention"
FULL = "full_attention"  # attends to the whole episode
CONV = "conv"  # a gated short convolution: no keys, a window of gated inputs
MAMBA = "mamba2"  # a Mamba-2 state-space mixer: no keys, a state per head and a window of convolution inputs
SPARSE = "sparse_attention"  # attends to the keys of the episode a learned indexer selects, `index_topk` a token
ATTENTION = (SLIDING, FULL, SPARSE)  # the kinds that cache keys and values
MOE = "moe"  # no mixer: a layer that is a sparse feed-forward alone (with ``mixer_ffn: False``)
FLOAT32_LEAVES = ("A_log", "dt_bias", "D")  # of a Mamba-2 layer: read in float32 wherever they are read, so never kept in less
Q_BLOCK = 64  # queries per attention block of a segment: 64 x (prefix + T) x 32 heads of float32 scores at a time
ROW_TILE = 512  # rows of one tile of the grouped product as XLA runs `ragged_dot` on the TPU
SPARSE_BLOCK_BYTES = 1 << 29  # float32 scores a block of a sparse layer's segment holds at a time


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int  # rows of the embedding and of the head held here (the slice)
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int  # the dense layers' feed-forward width
    moe_intermediate_size: int  # one expert's width (the shared expert's too, unless `shared_intermediate_size` says otherwise)
    num_experts: int  # the router's outputs: ALL experts of the layer
    num_experts_per_tok: int
    experts_held: Tuple[int, int]  # (first, count) of the experts whose weights live here
    layer_types: Tuple[str, ...]
    num_dense_layers: int  # leading layers with a dense feed-forward
    max_len: int  # positions a full-attention layer caches: the longest episode
    sliding_window: int = 0  # positions a sliding layer sees; a model without such a layer has none
    conv_L_cache: int = 3  # taps of a conv layer
    num_shared_experts: int = 1  # 0: no shared expert beside the routed ones
    shared_intermediate_size: int = 0  # the shared expert's own width; 0: `moe_intermediate_size * num_shared_experts`
    ffn_act: str = "silu_gated"  # every feed-forward: (silu(x W1) * (x W3)) W2, or "relu2": relu(x W1)^2 W2, two matrices
    mixer_ffn: bool = True  # a mixer layer carries a feed-forward; False: every layer is ONE part, a mixer or `moe`
    qk_norm: bool = True  # an RMS norm per head on queries and keys
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
    # a Mamba-2 layer (`mamba2` in `layer_types`): `ssm_heads x ssm_head_dim` inner channels, B and C in `ssm_groups`
    # groups of `ssm_state_size` (head h reads group h // (heads / groups)), a causal convolution of `ssm_conv_kernel`
    # taps with a bias over [x, B, C], a segment scanned in chunks of `ssm_chunk`; `ssm_dt_*`: the seeded step sizes
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state_size: int = 0
    ssm_conv_kernel: int = 4
    ssm_chunk: int = 128
    ssm_dt_min: float = 0.001
    ssm_dt_max: float = 0.1
    ssm_dt_floor: float = 1e-4
    # a learned sparse attention layer (`sparse_attention` in `layer_types`): an indexer of `index_heads` query heads of
    # `index_head_dim` on one shared key head scores every key of the episode, the attention reads the `index_topk`
    # best; rotary positions on the first `index_rope_dim` lanes of the indexer's queries and keys
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    index_rope_dim: int = 0
    route_score: str = "sigmoid"  # the router's scores: sigmoid with a selection bias, or softmax over all experts (no bias)

    @staticmethod
    def from_dict(d: Dict[str, Any], vocab_size: int, max_len: int) -> "DecoderConfig":
        fields = {f.name for f in dataclasses.fields(DecoderConfig)}
        kw = {k: v for k, v in d.items() if k in fields}
        kw["layer_types"] = tuple(kw["layer_types"])
        kw["rope_layers"] = tuple(kw.get("rope_layers", (SLIDING,)))
        unknown = set(kw["layer_types"]) - {*ATTENTION, CONV, MAMBA, MOE}
        if unknown or (SLIDING in kw["layer_types"] and not kw.get("sliding_window")):
            raise ValueError(f"layer_types {kw['layer_types']}: unknown kinds {sorted(unknown)}, or a sliding layer without sliding_window")
        if MOE in kw["layer_types"] and kw.get("mixer_ffn", True):
            raise ValueError("a `moe` layer is a feed-forward alone: it needs `mixer_ffn: False` (a mixer layer then carries none)")
        if MAMBA in kw["layer_types"] and (
            not (kw.get("ssm_heads") and kw.get("ssm_head_dim") and kw.get("ssm_state_size"))
            or kw["ssm_heads"] % kw.get("ssm_groups", 1) or kw["ssm_heads"] * kw["ssm_head_dim"] % kw.get("ssm_groups", 1)
        ):
            raise ValueError("a `mamba2` layer needs ssm_heads, ssm_head_dim and ssm_state_size, and ssm_groups that divides the heads")
        if SPARSE in kw["layer_types"] and not (kw.get("index_heads") and kw.get("index_head_dim") and kw.get("index_topk")):
            raise ValueError("a `sparse_attention` layer needs index_heads, index_head_dim and index_topk")
        if kw.get("route_score", "sigmoid") not in ("sigmoid", "softmax"):
            raise ValueError(f"route_score {kw['route_score']!r}: sigmoid or softmax")
        if kw.get("ffn_act", "silu_gated") not in ("silu_gated", "relu2"):
            raise ValueError(f"ffn_act {kw['ffn_act']!r}: silu_gated or relu2")
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
        """Where the carry keeps layer ``layer``'s state, among those of its kind (attention, conv, or state-space)."""
        kind = self.layer_types[layer]
        kinds = (kind,) if kind in (CONV, MAMBA) else ATTENTION
        return self.layers_of(*kinds).index(layer)

    def ffn_of(self, layer: int) -> Optional[str]:
        """The feed-forward layer ``layer`` carries: ``"mlp"`` (dense), ``"moe"``, or none (a mixer alone)."""
        if self.layer_types[layer] == MOE:
            return "moe"
        if not self.mixer_ffn:
            return None
        return "mlp" if layer < self.num_dense_layers else "moe"

    def moe_layers(self) -> Tuple[int, ...]:
        return tuple(i for i in range(len(self.layer_types)) if self.ffn_of(i) == "moe")

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels of a Mamba-2 layer's convolution: x, then B and C of every group."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state_size


# ----------------------------------------------------------------------------
# parameters and carry
# ----------------------------------------------------------------------------

def init_params(dc: DecoderConfig, key: jax.Array, std: Optional[float] = None) -> Params:
    """Seeded float32 parameters: normal(0, ``std``) matrices (``init_std`` unless given), unit norms, zero selection
    bias; of a Mamba-2 layer besides, as the family's ``_init_weights`` has them: taps uniform on
    ``+-1 / sqrt(ssm_conv_kernel)``, zero conv bias, ``A_log = log(1 .. heads)``, ``D`` ones, ``dt_bias`` the inverse
    softplus of a log-uniform draw on [``ssm_dt_min``, ``ssm_dt_max``] floored at ``ssm_dt_floor``."""
    std = dc.init_std if std is None else std
    H, D = dc.hidden_size, dc.head_dim
    Q, KV = dc.num_attention_heads * D, dc.num_key_value_heads * D
    keys = iter(jax.random.split(key, 16 * len(dc.layer_types) + 8))

    def mat(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32) * std

    def ffn(width, lead=()):
        if dc.ffn_act == "relu2":
            return {"w1": mat(*lead, H, width), "w2": mat(*lead, width, H)}
        return {"w1": mat(*lead, H, width), "w3": mat(*lead, H, width), "w2": mat(*lead, width, H)}

    params: Params = {"embed": mat(dc.vocab_size, H)}
    for i, kind in enumerate(dc.layer_types):
        part = dc.ffn_of(i)
        layer = {}
        norms = [("norm_in", "norm_post_attn")] * (kind != MOE) + [("norm_pre_mlp", "norm_post_mlp")] * bool(part)
        for before, after in norms:  # one pair for each part the layer has
            layer[before] = jnp.ones((H,))
            if dc.post_norms:
                layer[after] = jnp.ones((H,))
        if kind == CONV:  # conv_w[k] weighs the gated input `conv_L_cache - 1 - k` tokens back
            layer.update(w_in=mat(H, 3 * H), conv_w=mat(dc.conv_L_cache, H), w_out=mat(H, H))
        elif kind == MAMBA:  # w_in gives [z, xBC, dt]; conv_w[k] weighs the input `ssm_conv_kernel - 1 - k` tokens back
            K, heads = dc.ssm_conv_kernel, dc.ssm_heads
            lo, hi = math.log(dc.ssm_dt_min), math.log(dc.ssm_dt_max)
            dt = jnp.maximum(jnp.exp(jax.random.uniform(next(keys), (heads,)) * (hi - lo) + lo), dc.ssm_dt_floor)
            layer.update(
                w_in=mat(H, dc.ssm_inner + dc.ssm_conv_dim + heads),
                conv_w=jax.random.uniform(next(keys), (K, dc.ssm_conv_dim), jnp.float32, -1.0, 1.0) / math.sqrt(K),
                conv_b=jnp.zeros((dc.ssm_conv_dim,)), dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                A_log=jnp.log(jnp.arange(1, heads + 1, dtype=jnp.float32)), D=jnp.ones((heads,)),
                norm_gate=jnp.ones((dc.ssm_inner,)), w_out=mat(dc.ssm_inner, H),
            )
        elif kind != MOE:
            if dc.qk_norm:
                layer.update(q_norm=jnp.ones((D,)), k_norm=jnp.ones((D,)))
            layer.update(wq=mat(H, Q), wk=mat(H, KV), wv=mat(H, KV))
            if dc.attn_output_gate:
                layer["wg"] = mat(H, Q)
            layer["wo"] = mat(Q, H)
            if kind == SPARSE:  # the indexer: its queries, its one key head (layer-normed), the heads' weights
                d = dc.index_head_dim
                layer["index"] = {"wq": mat(H, dc.index_heads * d), "wk": mat(H, d), "norm": jnp.ones((d,)),
                                  "norm_bias": jnp.zeros((d,)), "ww": mat(H, dc.index_heads)}
        if part == "mlp":
            layer["mlp"] = ffn(dc.intermediate_size)
        elif part == "moe":
            layer["moe"] = {"router": mat(H, dc.num_experts)}
            if dc.route_score == "sigmoid":
                layer["moe"]["router_bias"] = jnp.zeros((dc.num_experts,))
            if dc.num_shared_experts:
                layer["moe"]["shared"] = ffn(dc.shared_intermediate_size or dc.moe_intermediate_size * dc.num_shared_experts)
            layer["moe"]["experts"] = ffn(dc.moe_intermediate_size, lead=(dc.experts_held[1],))
        params[f"layer_{i}"] = layer
    params.update(norm_out=jnp.ones((H,)), head=mat(H, dc.vocab_size), value_head=mat(H, 1))
    return params


def init_carry(dc: DecoderConfig, batch: int, dtype: Any = jnp.bfloat16) -> Carry:
    """``k`` and ``v`` hold one buffer per attention layer, ``ik`` one buffer of index keys per sparse layer, ``conv``
    one window per conv layer, ``ssm`` one state (float32 whatever ``dtype``) and ``ssm_window`` one window of
    convolution inputs per Mamba-2 layer, each in the layers' order; a kind the model lacks has no entry."""
    shape = lambda i: (batch, dc.cache_len(i), dc.num_key_value_heads * dc.head_dim)  # noqa: E731
    attn, conv, ssm = dc.layers_of(*ATTENTION), dc.layers_of(CONV), dc.layers_of(MAMBA)
    carry = {
        "k": [jnp.zeros(shape(i), dtype) for i in attn],
        "v": [jnp.zeros(shape(i), dtype) for i in attn],
        "pos": jnp.zeros((batch,), jnp.int32),
    }
    if dc.layers_of(SPARSE):
        carry["ik"] = [jnp.zeros((batch, dc.max_len, dc.index_head_dim), dtype) for _ in dc.layers_of(SPARSE)]
    if conv:
        carry["conv"] = [jnp.zeros((batch, dc.conv_L_cache - 1, dc.hidden_size), dtype) for _ in conv]
    if ssm:
        carry["ssm"] = [jnp.zeros((batch, dc.ssm_heads, dc.ssm_head_dim, dc.ssm_state_size), jnp.float32) for _ in ssm]
        carry["ssm_window"] = [jnp.zeros((batch, dc.ssm_conv_kernel - 1, dc.ssm_conv_dim), dtype) for _ in ssm]
    return carry


def carry_bytes(dc: DecoderConfig, dtype: Any = jnp.bfloat16) -> Dict[str, int]:
    """Bytes of recurrent carry one env holds, by kind of layer (and its position)."""
    shapes = jax.eval_shape(lambda: init_carry(dc, 1, dtype))
    size = lambda x: int(math.prod(x.shape)) * x.dtype.itemsize  # noqa: E731
    out = {"pos": size(shapes["pos"])}
    for i, k, v in zip(dc.layers_of(*ATTENTION), shapes["k"], shapes["v"]):
        out[dc.layer_types[i]] = out.get(dc.layer_types[i], 0) + size(k) + size(v)
    if "ik" in shapes:  # a sparse layer's index keys beside its keys and values
        out[SPARSE] += sum(size(z) for z in shapes["ik"])
    if "conv" in shapes:
        out[CONV] = sum(size(z) for z in shapes["conv"])
    if "ssm" in shapes:  # what a decode step reads and writes of it whole, every step: the state and the window
        out[MAMBA] = sum(size(z) for z in shapes["ssm"] + shapes["ssm_window"])
    return out


def update_router_bias(params: Params, load: jax.Array, dc: DecoderConfig) -> Params:
    """The selection bias after one update: ``b += coeff * sign(mean load - load_e)`` over the router's
    counts of that update (``load``: one row of ``num_experts`` counts per expert layer); a softmax router has none."""
    out = dict(params)
    if dc.route_score != "sigmoid":
        return out
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


def _attention_probs(q: jax.Array, k: jax.Array, mask: jax.Array) -> jax.Array:
    """q (B, T, KV, G, D), k (B, S, KV, D), mask (B, T, S) -> the softmax over the keys, (B, KV, G, T, S) float32."""
    scores = jnp.einsum("btkgd,bskd->bkgts", q, k, preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(q.shape[-1])
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    return jax.nn.softmax(scores, axis=-1)


def _weigh(probs: jax.Array, v: jax.Array) -> jax.Array:
    out = jnp.einsum("bkgts,bskd->btkgd", probs.astype(v.dtype), v)
    return out.reshape(out.shape[:2] + (-1,))


def _attend(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array) -> jax.Array:
    """q (B, T, KV, G, D), k/v (B, S, KV, D), mask (B, T, S) -> (B, T, KV*G*D); softmax in float32."""
    return _weigh(_attention_probs(q, k, mask), v)


def _per_head(cache: jax.Array, dc: DecoderConfig) -> jax.Array:
    """The carry's rows (B, S, KV * D) as ``_attend`` reads keys and values: (B, S, KV, D)."""
    return cache.reshape(cache.shape[:2] + (dc.num_key_value_heads, dc.head_dim))


def _ffn(w: Params, x: jax.Array, dot=jnp.matmul) -> jax.Array:
    """The feed-forward in the form its matrices state: ``(silu(x W1) * (x W3)) W2`` with three, ``relu(x W1)^2 W2``
    with two.  ``dot`` is the product: plain, or grouped over sorted rows (:func:`held_experts`)."""
    dt = x.dtype
    up = dot(x, w["w1"].astype(dt))
    h = jax.nn.silu(up) * dot(x, w["w3"].astype(dt)) if "w3" in w else jnp.square(jax.nn.relu(up))
    return dot(h, w["w2"].astype(dt))


def route(moe: Params, m: jax.Array, dc: DecoderConfig) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Scores over ALL experts: sigmoid, and the ``k`` largest of ``score + bias`` (the bias has no gradient), or
    (``route_score: softmax``) a softmax and its ``k`` largest; weights ``route_scale * s / (sum s + route_eps)``
    over the selected.  Returns (experts (N, k), weights (N, k) float32, counts (E,))."""
    logits = jnp.matmul(  # few columns: cheap in full precision, and a coarser product reorders near ties
        m.astype(jnp.float32), moe["router"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    if dc.route_score == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
        _, experts = jax.lax.top_k(s, dc.num_experts_per_tok)
    else:
        s = jax.nn.sigmoid(logits)
        _, experts = jax.lax.top_k(s + jax.lax.stop_gradient(moe["router_bias"].astype(jnp.float32)), dc.num_experts_per_tok)
    w = jnp.take_along_axis(s, experts, axis=-1)
    if dc.route_norm:
        w = w / (w.sum(axis=-1, keepdims=True) + dc.route_eps)
    counts = jnp.zeros((dc.num_experts,), jnp.int32).at[experts.reshape(-1)].add(1)
    return experts, w * dc.route_scale, counts


def rows_run(counts: jax.Array, rows: int, dc: DecoderConfig) -> jax.Array:
    """Sorted rows the grouped products of one pass over an expert layer visit, from the router's ``counts`` of
    that pass (..., E): the pairs routed to the held experts, which :func:`held_experts` sorts first, in whole
    tiles of ``ROW_TILE`` rows (of ``rows`` = ``tokens x k`` at most)."""
    first, held = dc.experts_held
    ours = counts[..., first:first + held].sum(-1)
    return jnp.minimum(-(-ours // ROW_TILE) * ROW_TILE, rows)


def held_experts(w: Params, m: jax.Array, experts: jax.Array, weights: jax.Array, dc: DecoderConfig) -> jax.Array:
    """The held experts' part of the layer's result, dropless: the ``N * k`` (token, expert) pairs are sorted by
    expert, those of experts held elsewhere last, and the held experts run as one grouped product over the pairs
    routed to them.  No capacity and no second program: whatever share of the pairs is routed here has its rows."""
    first, held = dc.experts_held
    N, k = experts.shape
    local = experts.reshape(-1) - first
    local = jnp.where((local >= 0) & (local < held), local, held)  # held elsewhere: after every group
    order = jnp.argsort(local, stable=True)
    groups = jnp.zeros((held + 1,), jnp.int32).at[local].add(1)[:held]
    token = order // k
    ours = (local[order] < held)[:, None]
    x = jnp.where(ours, jnp.take(m, token, axis=0), 0)
    dt = x.dtype

    def dot(rows, mats):
        # The rows of experts held elsewhere lie in NO group: a grouped product visits only the tiles that hold a
        # row of a group, so its time follows the pairs routed here and not `N * k`.  It also writes the rows of its
        # groups and nothing else (on the TPU the others keep whatever the buffer held, NaNs among it, in the
        # backward pass too), so every product's result is cut to the held rows by a select, which its transpose
        # repeats on the cotangent: nothing a product left unwritten reaches a sum, forward or backward.
        return jnp.where(ours, jax.lax.ragged_dot(rows, mats, groups), 0)

    y = jnp.where(ours, _ffn(w, x, dot).astype(jnp.float32) * weights.reshape(-1)[order][:, None], 0.0)
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
    if "q_norm" in layer:
        q = rms_norm(q, layer["q_norm"], dc.rms_norm_eps)
        k = rms_norm(k, layer["k_norm"], dc.rms_norm_eps)
    if rotary:
        q, k = rope(q, pos, dc.rope_theta), rope(k, pos, dc.rope_theta)
    return q, k, v


def _layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    centred = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    y = centred * jax.lax.rsqrt(jnp.mean(centred * centred, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def _index_qkw(index: Params, a: jax.Array, pos: jax.Array, dc: DecoderConfig):
    """The indexer on normed rows ``a`` (..., H) at positions ``pos`` (...), read as constants (the indexer takes its
    gradient from L_I alone and gives none to what made ``a``): queries (..., heads, d), the one key (..., d)
    layer-normed, rotary positions on the first ``index_rope_dim`` lanes of both, and the heads' weights (..., heads)
    in float32 with ``1 / sqrt(heads)`` in them."""
    a = jax.lax.stop_gradient(a)
    dt, lead, r = a.dtype, a.shape[:-1], dc.index_rope_dim
    q = (a @ index["wq"].astype(dt)).reshape(lead + (dc.index_heads, dc.index_head_dim))
    k = _layer_norm(a @ index["wk"].astype(dt), index["norm"], index["norm_bias"], dc.rms_norm_eps)
    if r:
        q = jnp.concatenate([rope(q[..., :r], pos, dc.rope_theta), q[..., r:]], axis=-1)
        k = jnp.concatenate([rope(k[..., :r], pos, dc.rope_theta), k[..., r:]], axis=-1)
    w = (a @ index["ww"].astype(dt)).astype(jnp.float32) / math.sqrt(dc.index_heads)
    return q, k, w


def index_scores(q: jax.Array, w: jax.Array, keys: jax.Array) -> jax.Array:
    """``I[b, t, s] = sum_j w[b, t, j] relu(q[b, t, j] . keys[b, s]) / sqrt(d)`` in float32: q (B, T, heads, d),
    w (B, T, heads), keys (B, S, d) -> (B, T, S); a score of nought is +0 (the selection orders it as a float)."""
    dots = jnp.einsum("bthd,bsd->bths", q, keys, preferred_element_type=jnp.float32)
    scores = jnp.sum(w[..., None] * jax.nn.relu(dots), axis=2) / math.sqrt(q.shape[-1])
    return jnp.where(scores == 0, 0.0, scores)


def top_mask(scores: jax.Array, visible: jax.Array, k: int) -> jax.Array:
    """The ``k`` highest ``scores`` (..., S) float32 among the ``visible`` (ties to the lower index) as a mask; every
    visible one where there are no more than ``k``.  The ``k``-th highest is found bit by bit on an unsigned key that
    orders as the floats do (32 counts over ``S``), and the ties at it are taken lowest index first."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    key = jnp.where(visible, jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31)), jnp.uint32(0))
    kth = jnp.zeros(scores.shape[:-1] + (1,), jnp.uint32)
    for bit in range(31, -1, -1):
        trial = kth | jnp.uint32(1 << bit)
        kth = jnp.where(jnp.sum(key >= trial, axis=-1, keepdims=True) >= k, trial, kth)
    above = key > kth
    tie = visible & (key == kth)
    need = k - jnp.sum(above, axis=-1, keepdims=True)
    return visible & (above | (tie & (jnp.cumsum(tie, axis=-1) <= need)))


def _sparse_block(B: int, T: int, S: int, heads: int) -> int:
    """Queries a block of a sparse layer's segment takes at a time: ``B x heads x block x S`` float32 scores under
    ``SPARSE_BLOCK_BYTES``, and a divisor of ``T``."""
    qb = max(1, min(T, SPARSE_BLOCK_BYTES // (4 * B * heads * S)))
    while T % qb:
        qb -= 1
    return qb


def _index_kl(p: jax.Array, scores: jax.Array, sel: jax.Array) -> jax.Array:
    """L_I of each query: ``KL(p || softmax of the index scores over the selected keys)``, ``p`` (B, T, S) the main
    attention's probabilities summed over its heads and normalised, a constant; scores, sel (B, T, S) -> (B, T)
    float32."""
    p = jax.lax.stop_gradient(p)
    log_q = jax.nn.log_softmax(jnp.where(sel, scores, -1e30), axis=-1)
    return jnp.sum(jnp.where(sel, jax.scipy.special.xlogy(p, p) - p * log_q, 0.0), axis=-1)


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


def _ssm_inputs(layer: Params, a: jax.Array, window: jax.Array, pos: jax.Array, dc: DecoderConfig):
    """Normed rows ``a`` (B, T, H) of a Mamba-2 layer -> the gate ``z`` (B, T, inner), the heads' inputs ``x``
    (B, T, heads, head_dim), ``B`` and ``C`` (B, T, groups, state), the step sizes ``dt`` (B, T, heads) in float32,
    and the convolution's inputs (B, K - 1 + T, conv_dim): ``window``, the carry's, then the tokens' own."""
    T = a.shape[1]
    z, xbc, dt = jnp.split(a @ layer["w_in"].astype(a.dtype), [dc.ssm_inner, dc.ssm_inner + dc.ssm_conv_dim], axis=-1)
    window = jnp.concatenate([window.astype(a.dtype), xbc], axis=1)
    xbc = jax.nn.silu(_conv_taps(layer, window, pos, T) + layer["conv_b"].astype(a.dtype))
    x, b, c = jnp.split(xbc, [dc.ssm_inner, dc.ssm_inner + dc.ssm_groups * dc.ssm_state_size], axis=-1)
    lead = a.shape[:2]
    x = x.reshape(lead + (dc.ssm_heads, dc.ssm_head_dim))
    b, c = (m.reshape(lead + (dc.ssm_groups, dc.ssm_state_size)) for m in (b, c))
    dt = jax.nn.softplus(dt.astype(jnp.float32) + layer["dt_bias"].astype(jnp.float32))
    return z, x, b, c, dt, window


def _ssm_output(layer: Params, y: jax.Array, z: jax.Array, dc: DecoderConfig) -> jax.Array:
    """``y`` (..., inner) float32 gated by ``silu(z)``, then RMS-normed in ``ssm_groups`` groups of channels (the
    gate first, then the norm), then the output projection."""
    g = y * jax.nn.silu(z.astype(jnp.float32))
    grouped = g.reshape(g.shape[:-1] + (dc.ssm_groups, -1))
    grouped = grouped * jax.lax.rsqrt(jnp.mean(grouped * grouped, axis=-1, keepdims=True) + dc.rms_norm_eps)
    g = (grouped.reshape(g.shape) * layer["norm_gate"].astype(jnp.float32)).astype(z.dtype)
    return g @ layer["w_out"].astype(z.dtype)


def _ssm_step(layer: Params, x, b, c, dt, pos, state, dc: DecoderConfig):
    """One token of the recurrence ``S = exp(dt A) S + dt x (outer) B``, ``y = S C + D x``, in float32: x (B, heads,
    head_dim), b, c (B, groups, state), dt (B, heads), state (B, heads, head_dim, state).  At position 0 the
    state before the token counts for nought.  -> y (B, inner) float32, the state after the token."""
    f32 = jnp.float32
    per_head = lambda m: jnp.repeat(m.astype(f32), dc.ssm_heads // dc.ssm_groups, axis=1)  # noqa: E731  (B, heads, state)
    x = x.astype(f32)
    decay = jnp.exp(dt * -jnp.exp(layer["A_log"].astype(f32)))
    state = jnp.where((pos == 0)[:, None, None, None], 0.0, state)
    state = decay[..., None, None] * state + (dt[..., None] * x)[..., None] * per_head(b)[:, :, None, :]
    y = jnp.sum(state * per_head(c)[:, :, None, :], axis=-1) + layer["D"].astype(f32)[:, None] * x
    return y.reshape(y.shape[0], -1), state


def _ssm_scan(layer: Params, x, b, c, dt, cuts, state, dc: DecoderConfig):
    """The same recurrence over ``T`` tokens in chunks of ``ssm_chunk``, from the carry's ``state`` (a constant):
    x (B, T, heads, head_dim), b, c (B, T, groups, state), dt (B, T, heads) float32 (nought at a token that is
    not real: it then leaves the state as it found it), ``cuts`` (B, T) the number of episode starts at or
    before each token (0: still the carry's episode).  Inside a chunk token ``i`` reads token ``j <= i`` through
    ``(C_i . B_j) exp(sum of dt A over (j, i])``; a chunk hands its successor the state at its end; a token reads
    the state its chunk was handed through the decay since the chunk's start.  A reset cuts all three: two tokens
    with different ``cuts`` do not see each other, and neither does a token see a state from before its episode
    (the carry's at the segment's first token, a chunk's at a later chunk's edge).  Cumulative sums and decays in
    float32, the products in the inputs' dtype with float32 accumulation.  -> y (B, T, inner) float32, the state
    after the last token."""
    f32 = jnp.float32
    B, T, heads, P = x.shape
    G, N, R = dc.ssm_groups, dc.ssm_state_size, dc.ssm_heads // dc.ssm_groups
    Q = min(dc.ssm_chunk, T)
    if T % Q:
        raise ValueError(f"a segment of {T} tokens does not divide into scan chunks of {Q}")
    chunks = lambda m: m.reshape((B, T // Q, Q) + m.shape[2:])  # noqa: E731
    dot = lambda eq, *ms: jnp.einsum(eq, *ms, preferred_element_type=f32)  # noqa: E731
    log_decay = chunks(dt * -jnp.exp(layer["A_log"].astype(f32)))  # (B, chunks, Q, heads), <= 0
    upto = jnp.cumsum(log_decay, axis=2)  # from the chunk's first token to this one, both included
    cut = chunks(cuts)
    handed = jnp.concatenate([jnp.zeros((B, 1), cuts.dtype), cuts[:, Q - 1:-1:Q]], axis=1)  # `cuts` before each chunk's first token
    xdt = chunks((x.astype(f32) * dt[..., None]).astype(x.dtype)).reshape(B, T // Q, Q, G, R, P)
    bq, cq = chunks(b), chunks(c)
    # inside a chunk
    sees = (cut[:, :, :, None] == cut[:, :, None, :]) & jnp.tril(jnp.ones((Q, Q), bool))  # (B, chunks, i, j)
    through = jnp.exp(jnp.where(sees[..., None], upto[:, :, :, None] - upto[:, :, None, :], -jnp.inf))  # (B, chunks, i, j, heads)
    weights = dot("bcign,bcjgn->bcijg", cq, bq)[..., None] * through.reshape(through.shape[:4] + (G, R))
    y = dot("bcijgr,bcjgrp->bcigrp", weights.astype(x.dtype), xdt)
    # what a chunk adds to the state at its end, and what it lets through of the state it was handed
    to_end = jnp.where(cut == cut[:, :, -1:], 1.0, 0.0)[..., None] * jnp.exp(upto[:, :, -1:] - upto)  # (B, chunks, j, heads)
    added = dot("bcjgrp,bcjgn->bcgrpn", (xdt * to_end.reshape(to_end.shape[:3] + (G, R, 1))).astype(x.dtype), bq)
    lets = jnp.where(cut[:, :, -1] == handed, 1.0, 0.0)[..., None] * jnp.exp(upto[:, :, -1])  # (B, chunks, heads)

    def edge(s, chunk):  # the state a chunk is handed, and the one it hands on
        add, let = chunk
        return let.reshape(B, G, R, 1, 1) * s + add, s

    last, starts = jax.lax.scan(edge, state.astype(f32).reshape(B, G, R, P, N), (jnp.moveaxis(added, 1, 0), jnp.moveaxis(lets, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)  # (B, chunks, G, R, P, N)
    since = jnp.where(cut == handed[..., None], 1.0, 0.0)[..., None] * jnp.exp(upto)  # (B, chunks, i, heads)
    y = y + dot("bcign,bcgrpn->bcigrp", cq, starts.astype(x.dtype)) * since.reshape(since.shape[:3] + (G, R, 1))
    y = y.reshape(B, T, heads, P) + layer["D"].astype(f32)[:, None] * x.astype(f32)
    return y.reshape(B, T, heads * P), last.reshape(B, heads, P, N)


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


# each env's row (B, ...) written into its cache (B, slots, lanes) at its slot (B,), in place where the cache is donated
_write_rows = jax.vmap(lambda c, s, row: jax.lax.dynamic_update_slice(c, row.reshape(1, -1).astype(c.dtype), (s, 0)))


def _scope(kind: str) -> str:
    return {SLIDING: "policy.attn.window", FULL: "policy.attn.full", CONV: "policy.conv", MAMBA: "policy.ssm",
            SPARSE: "policy.attn.sparse"}[kind]


# ----------------------------------------------------------------------------
# one token through the caches
# ----------------------------------------------------------------------------

def _step_mixer(layer: Params, x, pos, carry: Carry, new: Carry, dc: DecoderConfig, i: int, dtype: Any):
    """Layer ``i``'s mixer on one token an env, (B, H) rows at positions ``pos``: reads the layer's state in
    ``carry``, appends the state after the token to ``new``, returns the rows with the mixer's part added."""
    kind, n = dc.layer_types[i], dc.carry_slot(i)
    a = rms_norm(x, layer["norm_in"], dc.rms_norm_eps)
    if kind == CONV:
        z, gate = _conv_gates(layer, a)
        state = carry["conv"][n]
        window = jnp.concatenate([state.astype(dtype), z[:, None]], axis=1)
        y = gate * _conv_taps(layer, window, pos[:, None], 1)[:, 0]
        new["conv"].append(window[:, 1:].astype(state.dtype))  # the window moves on by one token
        return _after_mixer(layer, x, y @ layer["w_out"].astype(dtype), dc)
    if kind == MAMBA:
        z, xs, b, c, dt, window = _ssm_inputs(layer, a[:, None], carry["ssm_window"][n], pos[:, None], dc)
        with jax.named_scope("policy.ssm.scan"):
            y, state = _ssm_step(layer, xs[:, 0], b[:, 0], c[:, 0], dt[:, 0], pos, carry["ssm"][n], dc)
        new["ssm"].append(state)
        new["ssm_window"].append(window[:, 1:].astype(carry["ssm_window"][n].dtype))
        return _after_mixer(layer, x, _ssm_output(layer, y, z[:, 0], dc), dc)
    size = dc.cache_len(i)
    q, k, v = _qkv(layer, a, pos, kind in dc.rope_layers, dc)
    slot = jnp.mod(pos, size)
    ck, cv = _write_rows(carry["k"][n], slot, k), _write_rows(carry["v"][n], slot, v)
    if decode_attention.engages(size):  # a cache with blocks to skip is read as far as each env has written it
        # a ring keeps the last `size` positions and nothing older: unwrapped, and in a full cache, slots [0, pos]
        o = decode_attention.decode_attention(q, ck, cv, jnp.minimum(pos + 1, size)).astype(dtype)
    else:
        mask = slot_positions(pos, size) >= 0
        o = _attend(q[:, None], _per_head(ck, dc).astype(dtype), _per_head(cv, dc).astype(dtype), mask[:, None])[:, 0]
    new["k"].append(ck)
    new["v"].append(cv)
    return _after_attention(layer, x, a, o, dc)


def _step_sparse(layer: Params, x, pos, carry: Carry, new: Carry, dc: DecoderConfig, i: int, dtype: Any):
    """Layer ``i``'s learned sparse attention on one token an env, (B, H) rows at positions ``pos``: the token's index
    key is written, the indexer scores every position of the env's episode, the ``index_topk`` best are selected (ties
    to the lower position), and only their rows of the key and value caches are fetched and attended over.  Returns
    the rows with the mixer's part added and the selected slots (B, topk), -1 where fewer positions were written."""
    n, m, size = dc.carry_slot(i), dc.layers_of(SPARSE).index(i), dc.cache_len(i)
    slot = jnp.mod(pos, size)
    with jax.named_scope(_scope(SPARSE)):
        a = rms_norm(x, layer["norm_in"], dc.rms_norm_eps)
    with jax.named_scope("policy.attn.index"):
        qi, ki, wi = _index_qkw(layer["index"], a, pos, dc)
        cik = _write_rows(carry["ik"][m], slot, ki)
        scores = index_scores(qi[:, None], wi[:, None], cik.astype(dtype))[:, 0]
        visible = slot_positions(pos, size) >= 0
        _, rows = jax.lax.top_k(jnp.where(visible, scores, -jnp.inf), min(dc.index_topk, size))
        chosen = jnp.take_along_axis(visible, rows, axis=1)
    with jax.named_scope(_scope(SPARSE)):
        q, k, v = _qkv(layer, a, pos, SPARSE in dc.rope_layers, dc)
        ck, cv = _write_rows(carry["k"][n], slot, k), _write_rows(carry["v"][n], slot, v)
        fetch = lambda cache: _per_head(jnp.take_along_axis(cache, rows[..., None], axis=1), dc).astype(dtype)  # noqa: E731
        o = _attend(q[:, None], fetch(ck), fetch(cv), chosen[:, None])[:, 0]
        x = _after_attention(layer, x, a, o, dc)
    new["k"].append(ck)
    new["v"].append(cv)
    new["ik"].append(cik)
    return x, jnp.where(chosen, rows, -1)


def step(params: Params, dc: DecoderConfig, carry: Carry, tokens: jax.Array, is_first: jax.Array, dtype: Any,
         selected: Optional[list] = None):
    """``tokens`` (B,), ``is_first`` (B,) -> (carry', logits (B, V), value (B, 1)).  A reset empties the
    env's caches (its position goes to nought) before the token is read.  ``selected``, a list, receives each
    sparse layer's selected slots (B, topk)."""
    pos = jnp.where(is_first > 0, 0, carry["pos"]).astype(jnp.int32)
    x = _embed(params, tokens, dc, dtype)
    new: Carry = {"k": [], "v": [], "ik": [], "conv": [], "ssm": [], "ssm_window": []}
    for i, kind in enumerate(dc.layer_types):
        layer = params[f"layer_{i}"]
        if kind == SPARSE:  # its indexer and its attention under scopes of their own
            x, rows = _step_sparse(layer, x, pos, carry, new, dc, i, dtype)
            if selected is not None:
                selected.append(rows)
        elif kind != MOE:  # a `moe` layer is a feed-forward alone
            with jax.named_scope(_scope(kind)):
                x = _step_mixer(layer, x, pos, carry, new, dc, i, dtype)
        if dc.ffn_of(i):
            x, _ = _mlp(layer, x, dc)
    logits, value = _heads(params, x, dc)
    return _next_carry(carry, new, pos + 1), logits, value


# ----------------------------------------------------------------------------
# a segment on a cached prefix
# ----------------------------------------------------------------------------

def _segment_ffn(layer: Params, x, dc: DecoderConfig):
    """The layer's feed-forward over (B, T, H) rows, where it carries one: (x', router counts or None)."""
    if "mlp" not in layer and "moe" not in layer:  # graftlint: disable=trace-python-branch  (keys of the dict, not values: a mixer layer of a model whose layers are one part)
        return x, None
    B, T, H = x.shape
    y, counts = _mlp(layer, x.reshape(B * T, H), dc)
    return y.reshape(B, T, H), counts


def _visible(pos, seg, prefix_pos, sliding: bool, dc: DecoderConfig) -> jax.Array:
    """(B, T, prefix + T): the keys each query of a segment sees, the prefix's (the carry's episode, for queries
    before the segment's first reset) and the segment's own (its episode, not later than the query), within the
    window of a sliding layer."""
    T = pos.shape[1]
    t = jnp.arange(T, dtype=jnp.int32)
    on_prefix = (seg[:, :, None] == 0) & (prefix_pos[:, None, :] >= 0)
    own = (t[None, :, None] >= t[None, None, :]) & (seg[:, :, None] == seg[:, None, :])
    if sliding:
        on_prefix &= pos[:, :, None] - prefix_pos[:, None, :] < dc.sliding_window
        own &= (t[:, None] - t[None, :] < dc.sliding_window)[None]
    return jnp.concatenate([on_prefix, own], axis=2)


def _query_blocks(z: jax.Array, qb: int) -> jax.Array:
    """(B, T, ...) -> (T / qb, B, qb, ...)."""
    B, T = z.shape[:2]
    return jnp.moveaxis(z.reshape((B, T // qb, qb) + z.shape[2:]), 1, 0)


def _segment_select(layer: Params, x, pos, seg, prefix_ik, prefix_pos, dc: DecoderConfig) -> jax.Array:
    """A sparse layer's selection for every query of a segment over (B, T, H) rows, made once a layer and a pass and
    differentiated through by nothing: the indexer's scores over the carry's prefix (its index keys, constants) and
    the segment's own, the ``index_topk`` best of those each query sees.  -> (B, T, prefix + T) bool."""
    layer, x = jax.lax.stop_gradient((layer, x))
    B, T, _ = x.shape
    with jax.named_scope("policy.attn.index"):
        a = rms_norm(x, layer["norm_in"], dc.rms_norm_eps)
        qi, ki, wi = _index_qkw(layer["index"], a, pos, dc)
        keys = jnp.concatenate([prefix_ik.astype(x.dtype), ki], axis=1)
        visible = _visible(pos, seg, prefix_pos, False, dc)
        qb = _sparse_block(B, T, keys.shape[1], dc.index_heads)
        select = lambda args: top_mask(index_scores(args[0], args[1], keys), args[2], dc.index_topk)  # noqa: E731
        sel = jax.lax.map(select, (_query_blocks(qi, qb), _query_blocks(wi, qb), _query_blocks(visible, qb)))
    return jnp.moveaxis(sel, 0, 1).reshape(visible.shape)


def _sparse_prefix(q, k, v, prefix_k, prefix_v, sel):
    """A sparse layer's attention where the carry's prefix (B, S, KV, D) engages ``segment_attention``: its kernel
    attends over the prefix's selected keys, each env's only as far as some query selected one, XLA over the
    segment's own ``T``, and the two merge through their log-sum-exp into the softmax over the same selected set.
    Returns the output (B, T, KV * G * D) in the queries' dtype, the heads' mean of the probabilities (B, T, S + T), a
    constant for L_I, and the key blocks of the prefix the kernels read."""
    B, S = prefix_k.shape[:2]
    dt = q.dtype
    rows = lambda cache: cache.reshape(B, S, -1).astype(dt)  # noqa: E731  (the carry's rows as stored)
    sel_p, own = sel[..., :S], sel[..., S:][:, None, None]
    o_p, lse_p = segment_attention.attend(q, rows(prefix_k), rows(prefix_v), sel_p)
    lse_p = jnp.moveaxis(lse_p, 1, -1)  # (B, KV, G, T): -inf where a query selected nothing on the prefix
    scores = jnp.einsum("btkgd,bskd->bkgts", q, k, preferred_element_type=jnp.float32) / math.sqrt(q.shape[-1])
    scores = jnp.where(own, scores, segment_attention.MASKED)
    top = jax.lax.stop_gradient(jnp.maximum(lse_p, scores.max(axis=-1)))  # every query selected a key somewhere
    e = jnp.exp(scores - top[..., None])
    w = jnp.exp(lse_p - top)
    z = w + e.sum(axis=-1)
    probs = e / z[..., None]  # the segment's own columns of the softmax
    o = jnp.einsum("bkgts,bskd->btkgd", probs.astype(v.dtype), v, preferred_element_type=jnp.float32)
    o = o + jnp.moveaxis(w / z, -1, 1)[..., None] * o_p
    with jax.named_scope("policy.attn.index_loss"):
        lse = jax.lax.stop_gradient(jnp.moveaxis(top + jnp.log(z), -1, 1))
        p = jnp.concatenate([segment_attention.head_mean(q, rows(prefix_k), sel_p, lse), jnp.mean(probs, axis=(1, 2))], axis=-1)
    return o.reshape(o.shape[:2] + (-1,)).astype(dt), p, segment_attention.blocks_read(sel_p)


def _segment_sparse_layer(layer: Params, x, pos, prefix_k, prefix_v, prefix_ik, sel, dc: DecoderConfig):
    """One sparse attention layer over (B, T, H) rows: each query attends over the keys ``sel`` (B, T, prefix + T)
    selected for it, and gives its term of L_I over them.  A prefix that ``segment_attention`` engages on is read by
    its kernels (:func:`_sparse_prefix`); a shorter one is a mask on the blocked product.  Returns (x', router counts
    or None, k, v and index keys of the segment, L_I (B, T) float32, key blocks of the prefix the kernels read or
    None)."""
    B, T, H = x.shape
    dt = x.dtype
    with jax.named_scope(_scope(SPARSE)):
        a = rms_norm(x, layer["norm_in"], dc.rms_norm_eps)
        q, k, v = _qkv(layer, a, pos, SPARSE in dc.rope_layers, dc)
    with jax.named_scope("policy.attn.index"):
        qi, ki, wi = _index_qkw(layer["index"], a, pos, dc)
        index_keys = jnp.concatenate([prefix_ik.astype(dt), ki], axis=1)

    def index_kl(qi_b, wi_b, sel_b, p_b):
        with jax.named_scope("policy.attn.index_loss"):
            return _index_kl(p_b, index_scores(qi_b, wi_b, index_keys), sel_b)

    if segment_attention.engages(prefix_k.shape[1]):
        with jax.named_scope(_scope(SPARSE)):
            o, p, read = _sparse_prefix(q, k, v, prefix_k, prefix_v, sel)
        qb = _sparse_block(B, T, index_keys.shape[1], dc.index_heads)
        kl = jax.lax.map(jax.checkpoint(lambda args: index_kl(*args)), tuple(_query_blocks(z, qb) for z in (qi, wi, sel, p)))
    else:
        read = None
        with jax.named_scope(_scope(SPARSE)):
            keys = jnp.concatenate([prefix_k.astype(dt), k], axis=1)
            values = jnp.concatenate([prefix_v.astype(dt), v], axis=1)

        def block(args):
            q_b, qi_b, wi_b, sel_b = args
            with jax.named_scope(_scope(SPARSE)):
                probs = _attention_probs(q_b, keys, sel_b)
                o_b = _weigh(probs, values)
            return o_b, index_kl(qi_b, wi_b, sel_b, jnp.mean(probs, axis=(1, 2)))

        qb = _sparse_block(B, T, keys.shape[1], max(dc.num_attention_heads, dc.index_heads))
        o, kl = jax.lax.map(jax.checkpoint(block), tuple(_query_blocks(z, qb) for z in (q, qi, wi, sel)))
        o = jnp.moveaxis(o, 0, 1).reshape(B, T, -1)
    with jax.named_scope(_scope(SPARSE)):
        x = _after_attention(layer, x, a, o, dc)
    return _segment_ffn(layer, x, dc) + (k, v, ki, jnp.moveaxis(kl, 0, 1).reshape(B, T), read)


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
        mask = _visible(pos, seg, prefix_pos, sliding, dc)
        qb = min(Q_BLOCK, T)
        if T % qb:
            raise ValueError(f"a segment of {T} tokens does not divide into query blocks of {qb}")
        blocks = lambda z: jnp.moveaxis(z.reshape((B, T // qb, qb) + z.shape[2:]), 1, 0)  # noqa: E731
        attend = jax.checkpoint(lambda qm: _attend(qm[0], keys, values, qm[1]))
        o = jax.lax.map(attend, (blocks(q), blocks(mask)))
        o = jnp.moveaxis(o, 0, 1).reshape(B, T, -1)
        x = _after_attention(layer, x, a, o, dc)
    return _segment_ffn(layer, x, dc) + (k, v)


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
    return _segment_ffn(layer, x, dc) + (window,)


def _segment_ssm_layer(layer: Params, x, pos, cuts, dt_mask, state, window, dc: DecoderConfig):
    """One Mamba-2 layer over (B, T, H) rows, from the carry's ``state`` and ``window`` (B, K - 1, conv_dim); ``cuts``
    as :func:`_ssm_scan` reads them, ``dt_mask`` (B, T) nought at a token that is not real.  Returns (x', router
    counts or None, the state after the last real token, the convolution's inputs: the carry's rows, then the segment's)."""
    with jax.named_scope(_scope(MAMBA)):
        a = rms_norm(x, layer["norm_in"], dc.rms_norm_eps)
        z, xs, b, c, dt, window = _ssm_inputs(layer, a, window, pos, dc)
        with jax.named_scope("policy.ssm.scan"):
            y, state = _ssm_scan(layer, xs, b, c, dt * dt_mask[..., None], cuts, state, dc)
        x = _after_mixer(layer, x, _ssm_output(layer, y, z, dc), dc)
    return _segment_ffn(layer, x, dc) + (state, window)


def segment(
    params: Params, dc: DecoderConfig, carry: Carry, tokens: jax.Array, is_first: jax.Array, dtype: Any,
    extend: bool = False, valid: Optional[jax.Array] = None, index_loss: bool = False, read: Optional[list] = None,
):
    """``tokens``, ``is_first`` (T, B) on the prefix cached in ``carry`` (constants: nothing is
    differentiated through them) -> (logits (T, B, V), values (T, B, 1), router counts (expert layers,
    E)), and with ``extend`` the carry that holds the segment as well.  ``valid`` (B,), with ``extend``:
    only each env's first ``valid`` tokens are real (a ragged prefill); the others are not written.
    ``index_loss`` (without ``extend``) adds L_I of every token (T, B), summed over the sparse layers (None
    for a model without one).  ``read``, a list, receives for each sparse layer whose prefix the
    ``segment_attention`` kernels read the key blocks they fetched (an env's blocks that some query selected from)."""
    carry = jax.lax.stop_gradient(carry)
    pos_tb, seg_tb = segment_positions(is_first, carry["pos"])
    pos, seg = pos_tb.T, seg_tb.T  # (B, T)
    x = _embed(params, tokens.T, dc, dtype)
    T = x.shape[1]
    if extend:  # real tokens an env
        n = jnp.full(pos.shape[:1], T, jnp.int32) if valid is None else valid.astype(jnp.int32)
    counts, kl = [], []
    new: Carry = {"k": [], "v": [], "ik": [], "conv": [], "ssm": [], "ssm_window": []}
    keep = jax.vmap(lambda rows, start, size: jax.lax.dynamic_slice_in_dim(rows, start, size, axis=0), in_axes=(0, 0, None))
    if dc.layers_of(MAMBA):  # an episode starts at position 0, at a real token: the state before it counts for nought
        live = jnp.arange(T)[None] < n[:, None] if extend else jnp.ones(pos.shape, bool)
        cuts = jnp.cumsum((pos == 0) & live, axis=1, dtype=jnp.int32)
    for i, kind in enumerate(dc.layer_types):
        if kind == MOE:
            x, c = jax.checkpoint(_segment_ffn, static_argnums=(2,))(params[f"layer_{i}"], x, dc)
        elif kind == MAMBA:
            at = dc.carry_slot(i)
            state, window = carry["ssm"][at], carry["ssm_window"][at]
            run = jax.checkpoint(_segment_ssm_layer, static_argnums=(7,))
            x, c, last, inputs = run(params[f"layer_{i}"], x, pos, cuts, live.astype(jnp.float32), state, window, dc)
            if extend:  # the state after, and the convolution's inputs at, the last K - 1 real tokens
                new["ssm"].append(last)
                new["ssm_window"].append(keep(inputs, n, window.shape[1]).astype(window.dtype))
        elif kind == CONV:
            state = carry["conv"][dc.carry_slot(i)]
            run = jax.checkpoint(_segment_conv_layer, static_argnums=(4,))
            x, c, window = run(params[f"layer_{i}"], x, pos, state, dc)
            if extend:  # the gated inputs of the last L - 1 real tokens; the carry's own rows where there are fewer
                new["conv"].append(keep(window, n, state.shape[1]).astype(state.dtype))
        else:
            at, size = dc.carry_slot(i), dc.cache_len(i)
            prefix_pos = slot_positions(carry["pos"] - 1, size)
            prefix_k, prefix_v = _per_head(carry["k"][at], dc), _per_head(carry["v"][at], dc)
            if kind == SPARSE:
                prefix_ik = carry["ik"][dc.layers_of(SPARSE).index(i)]
                sel = _segment_select(params[f"layer_{i}"], x, pos, seg, prefix_ik, prefix_pos, dc)
                run = jax.checkpoint(_segment_sparse_layer, static_argnums=(7,))
                x, c, k, v, ik, layer_kl, blocks = run(params[f"layer_{i}"], x, pos, prefix_k, prefix_v, prefix_ik, sel, dc)
                kl.append(layer_kl)
                if read is not None and blocks is not None:
                    read.append(blocks)
            else:
                run = jax.checkpoint(_segment_layer, static_argnums=(7, 8))
                x, c, k, v = run(params[f"layer_{i}"], x, pos, seg, prefix_k, prefix_v, prefix_pos, dc, kind)
            if extend:
                if T > size:
                    raise ValueError("a prefill segment longer than the window would write a slot twice")
                real = jnp.arange(T)[None] < n[:, None]
                slot = jnp.where(real, jnp.mod(pos, size), size)  # out of range: dropped
                put = jax.vmap(lambda cache, s, rows: cache.at[s].set(rows.reshape(T, -1).astype(cache.dtype), mode="drop"))
                new["k"].append(put(carry["k"][at], slot, k))
                new["v"].append(put(carry["v"][at], slot, v))
                if kind == SPARSE:
                    new["ik"].append(put(prefix_ik, slot, ik))
        if c is not None:
            counts.append(c)
    logits, values = _heads(params, x, dc)
    logits, values = jnp.moveaxis(logits, 0, 1), jnp.moveaxis(values, 0, 1)
    load = jnp.stack(counts) if counts else jnp.zeros((0, dc.num_experts), jnp.int32)
    if not extend:
        if index_loss:
            return logits, values, load, (sum(kl).T if kl else None)
        return logits, values, load
    last = jnp.take_along_axis(pos, jnp.maximum(n - 1, 0)[:, None], axis=1)[:, 0]
    new_pos = jnp.where(n > 0, last + 1, carry["pos"])
    return logits, values, load, _next_carry(carry, new, new_pos)
