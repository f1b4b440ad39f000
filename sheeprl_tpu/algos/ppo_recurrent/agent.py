"""Recurrent PPO agent (flax LSTM).

Capability parity with the reference agent
(reference: sheeprl/algos/ppo_recurrent/agent.py:18-470): feature MLP over
observations concatenated with one-hot previous actions, optional pre/post
RNN projections, an LSTM whose state carries across steps, and actor/critic
heads on the LSTM output.

TPU-first: the time loop is ALWAYS a ``lax.scan`` over the fused step
function, with the done-mask resetting the carried state inside the scan —
so training consumes full ``(T, B)`` rollouts with static shapes and needs
none of the reference's per-episode splitting/padding machinery
(reference: agent.py:237-263).
"""

from __future__ import annotations

from collections import Counter
from typing import Any, Dict, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.models import decoder
from sheeprl_tpu.models.models import MLP
from sheeprl_tpu.ops import decode_attention, segment_attention
from sheeprl_tpu.telemetry.recorder import RECORDER


class RecurrentPPOAgent(nn.Module):
    actions_dim: Tuple[int, ...]
    is_continuous: bool
    mlp_keys: Tuple[str, ...]
    encoder_units: int
    mlp_layers: int
    dense_act: str
    layer_norm: bool
    lstm_size: int
    pre_rnn: Dict[str, Any]
    post_rnn: Dict[str, Any]
    actor_cfg: Dict[str, Any]
    critic_cfg: Dict[str, Any]
    dtype: Any = jnp.float32

    def setup(self) -> None:
        self.encoder = MLP(
            hidden_sizes=(self.encoder_units,) * self.mlp_layers,
            activation=self.dense_act,
            layer_norm=self.layer_norm,
            dtype=self.dtype,
            name="encoder",
        )
        if self.pre_rnn.get("apply"):
            self.pre_mlp = MLP(
                hidden_sizes=(self.pre_rnn["dense_units"],),
                activation=self.pre_rnn.get("activation", "relu"),
                layer_norm=self.pre_rnn.get("layer_norm", False),
                dtype=self.dtype,
                name="pre_rnn_mlp",
            )
        if self.post_rnn.get("apply"):
            self.post_mlp = MLP(
                hidden_sizes=(self.post_rnn["dense_units"],),
                activation=self.post_rnn.get("activation", "relu"),
                layer_norm=self.post_rnn.get("layer_norm", False),
                dtype=self.dtype,
                name="post_rnn_mlp",
            )
        self.cell = nn.OptimizedLSTMCell(self.lstm_size, name="lstm")
        self.actor = MLP(
            hidden_sizes=(self.actor_cfg.get("dense_units", 64),) * self.actor_cfg.get("mlp_layers", 1),
            output_dim=sum(self.actions_dim) * (2 if self.is_continuous else 1),
            activation=self.actor_cfg.get("dense_act", "relu"),
            layer_norm=self.actor_cfg.get("layer_norm", False),
            dtype=self.dtype,
            name="actor",
        )
        self.critic = MLP(
            hidden_sizes=(self.critic_cfg.get("dense_units", 64),) * self.critic_cfg.get("mlp_layers", 1),
            output_dim=1,
            activation=self.critic_cfg.get("dense_act", "relu"),
            layer_norm=self.critic_cfg.get("layer_norm", False),
            dtype=self.dtype,
            name="critic",
        )

    def _features(self, obs: Dict[str, jax.Array], prev_actions: jax.Array) -> jax.Array:
        vec = jnp.concatenate([obs[k] for k in self.mlp_keys] + [prev_actions], axis=-1)
        x = self.encoder(vec)
        if self.pre_rnn.get("apply"):
            x = self.pre_mlp(x)
        return x

    def step(
        self,
        carry: Tuple[jax.Array, jax.Array],
        obs: Dict[str, jax.Array],
        prev_actions: jax.Array,
        is_first: jax.Array,
    ) -> Tuple[Tuple[jax.Array, jax.Array], Tuple[jax.Array, jax.Array]]:
        """One recurrent step for a ``(B, ...)`` batch; ``is_first`` (B, 1)
        zeroes the carried state at episode starts
        (``reset_recurrent_state_on_done`` semantics)."""
        c, h = carry
        mask = 1.0 - is_first
        c, h = c * mask, h * mask
        x = self._features(obs, prev_actions)
        (c, h), out = self.cell((c, h), x)
        if self.post_rnn.get("apply"):
            out = self.post_mlp(out)
        actor_out = self.actor(out).astype(jnp.float32)
        value = self.critic(out).astype(jnp.float32)
        return (c, h), (actor_out, value)

    def __call__(
        self,
        obs_seq: Dict[str, jax.Array],
        prev_actions_seq: jax.Array,
        is_first_seq: jax.Array,
        initial_state: Tuple[jax.Array, jax.Array],
    ) -> Tuple[jax.Array, jax.Array]:
        """Scan over a ``(T, B, ...)`` sequence; returns (T, B, ·) heads.

        The time loop is flax's LIFTED scan: a raw ``jax.lax.scan`` over a
        bound method trips linen's trace-level check (JaxTransformError —
        submodule access from inside a jax transform); ``nn.scan`` with
        ``variable_broadcast='params'`` shares the step's parameters across
        the unrolled time axis, which is exactly the recurrent semantics."""

        def body(mdl: "RecurrentPPOAgent", carry, xs):
            obs_t, act_t, first_t = xs
            return mdl.step(carry, obs_t, act_t, first_t)

        scan = nn.scan(
            body,
            variable_broadcast="params",
            split_rngs={"params": False},
            in_axes=0,
            out_axes=0,
        )
        _, (actor_out, values) = scan(
            self, initial_state, (obs_seq, prev_actions_seq, is_first_seq)
        )
        return actor_out, values

    def initial_state(self, batch: int) -> Tuple[jax.Array, jax.Array]:
        return (
            jnp.zeros((batch, self.lstm_size), self.dtype),
            jnp.zeros((batch, self.lstm_size), self.dtype),
        )


def one_hot_actions(actions: jax.Array, actions_dim: Sequence[int], is_continuous: bool) -> jax.Array:
    """Encode stored actions for the next-step input: one-hot per discrete
    branch, identity for continuous (reference feeds prev actions likewise)."""
    if is_continuous:
        return actions
    parts = [
        jax.nn.one_hot(actions[..., i].astype(jnp.int32), d, dtype=jnp.float32)
        for i, d in enumerate(actions_dim)
    ]
    return jnp.concatenate(parts, axis=-1)


class LSTMCore:
    """What the recurrent PPO loop asks of its core (``algo.core``), answered for the LSTM agent.

    The loop drives a core through these calls alone and does not know which one it holds:
    ``policy_step`` (one step on the carry), ``policy_segment`` (a rollout's segment from the carry at its
    start; a third result where the core has a router: its counts; a fourth where it has a loss of its own beside
    PPO's: that loss per step; a fifth where a kernel of its pass reads its carry as far as the pass needs: the
    blocks read and held), ``encode_prev`` (the action as the next
    step's input), ``acting_params`` (the weights as the rollout reads them), ``initial_state``,
    ``stores_values`` (the rollout keeps its values: no second pass over every token), and for a core with
    more to tell or to keep: ``init_aux``/``after_update`` (state that moves with every update),
    ``rollout_stats`` (what a dispatch gives out beside its losses), ``host_counts`` (what the loop counts on
    its ``stats.pull`` span), ``prefill`` (a carry filled from an episode so far)."""

    stores_values = False

    def __init__(self, agent: RecurrentPPOAgent):
        self.agent = agent
        self.prev_action_width = int(sum(agent.actions_dim))

    def policy_step(self, p, carry, obs, prev_actions, is_first):
        return self.agent.apply(
            p, method=RecurrentPPOAgent.step, carry=carry, obs=obs,
            prev_actions=prev_actions, is_first=is_first,
        )

    def policy_segment(self, p, obs_seq, prev_actions_seq, is_first_seq, carry):
        return self.agent.apply(p, obs_seq, prev_actions_seq, is_first_seq, carry) + (None, None, None)

    def encode_prev(self, actions):
        return one_hot_actions(actions, self.agent.actions_dim, self.agent.is_continuous)

    def acting_params(self, p):
        return p

    def initial_state(self, batch: int):
        size = self.agent.lstm_size
        return jnp.zeros((batch, size), jnp.float32), jnp.zeros((batch, size), jnp.float32)

    def init_aux(self):
        return None

    def rollout_stats(self, rollout, init_carry, venv, actor) -> Dict[str, Any]:
        return {}

    def host_counts(self, stats) -> Dict[str, Any]:
        return {}


class DecoderPPOAgent:
    """The decoder core (``algo.core: decoder``): a token-level policy over ``models/decoder.py``.

    It answers the calls of :class:`LSTMCore` itself.  The observation is the one integer key
    ``mlp_keys[0]``; the recurrent carry is the decoder's caches, convolution windows, state-space states and
    positions; the previous action is not read (the env's observation is the token emitted last)."""

    stores_values = True
    prev_action_width = 1  # the action itself: no one-hot of the vocabulary

    def __init__(self, config: Any, mlp_keys: Tuple[str, ...], dtype: Any = jnp.float32):
        if len(mlp_keys) != 1:
            raise ValueError(f"the decoder core reads one integer observation, got mlp_keys={mlp_keys}")
        self.config, self.key, self.dtype = config, mlp_keys[0], dtype
        self.carry_dtype = jnp.float32 if dtype == jnp.float32 else jnp.bfloat16
        self.window = config.sliding_window if config.layers_of(decoder.SLIDING) else None
        # a longer prefill segment would write a ring's slot twice; a model without a ring has no such bound
        self.prefill_chunk = self.window or config.max_len
        self.carry_bytes = decoder.carry_bytes(config, self.carry_dtype)  # an env, by kind of layer
        sizes = [config.cache_len(i) for i in config.layers_of(decoder.SLIDING, decoder.FULL)]
        self.sparse = len(config.layers_of(decoder.SPARSE))  # layers that read the rows their indexer selects
        self.cache_held = sum(sizes) + self.sparse * config.max_len  # positions a decode step's attention layers hold an env
        self.ragged_sizes = [s for s in sizes if decode_attention.engages(s)]  # of the layers read as far as written
        # a sparse layer's prefix that the update reads through `ops/segment_attention.py`, as far as its queries selected
        self.ragged_prefix = bool(self.sparse) and segment_attention.engages(config.max_len)
        # bytes of one position's index key, which a sparse layer's decode step scores at every written position
        self.index_key_bytes = config.index_head_dim * jnp.dtype(self.carry_dtype).itemsize
        # bytes of state and convolution window a decode step reads, and writes again, of an env's Mamba-2 layers
        self.ssm_bytes = self.carry_bytes.get(decoder.MAMBA, 0)

    def init(self, rng: jax.Array) -> Dict[str, Any]:
        return {"params": decoder.init_params(self.config, rng)}

    def initial_state(self, batch: int) -> Dict[str, Any]:
        return decoder.init_carry(self.config, batch, self.carry_dtype)

    def policy_step(self, p, carry, obs, prev_actions, is_first):
        """One decode step; a model with sparse layers also tells the rollout the slots each selected (B, layers,
        topk), which ``correct`` compares with the reference's choice."""
        selected = []
        carry, logits, value = decoder.step(
            p["params"], self.config, carry, obs[self.key][..., 0], is_first[..., 0], self.dtype, selected
        )
        if selected:
            return carry, (logits, value, {"selected": jnp.stack(selected, axis=1)})
        return carry, (logits, value)

    def policy_segment(self, p, obs_seq, prev_actions_seq, is_first_seq, carry):
        """The segment's logits, values, router counts and L_I, and where the sparse layers' prefix goes through
        ``segment_attention``: the key blocks its kernels read and the blocks the pass's envs held (2,)."""
        read = []
        out = decoder.segment(
            p["params"], self.config, carry, obs_seq[self.key][..., 0], is_first_seq[..., 0], self.dtype, index_loss=True,
            read=read,
        )
        if not read:
            return out + (None,)
        held = obs_seq[self.key].shape[1] * len(read) * (self.config.max_len // segment_attention.BLOCK)
        return out + (jnp.stack([sum(read), jnp.asarray(held, jnp.int32)]),)

    def prefill(self, p, carry, tokens, valid):
        """``carry`` with the first ``valid`` (B,) of the ``(T, B)`` ``tokens`` written into it."""
        first = jnp.zeros(tokens.shape, jnp.float32)
        return decoder.segment(p["params"], self.config, carry, tokens, first, self.dtype, extend=True, valid=valid)[3]

    def encode_prev(self, actions):
        return actions

    def acting_params(self, p):
        """The weights in the compute dtype once, not at every one of the rollout's steps; what sets a state-space
        layer's step sizes and decays stays float32 (``decoder.FLOAT32_LEAVES``)."""
        return jax.tree_util.tree_map_with_path(
            lambda path, x: x if path[-1].key in decoder.FLOAT32_LEAVES else x.astype(self.dtype), p)

    def init_aux(self) -> Dict[str, Any]:
        """What a dispatch's updates tell of the expert layers: the router's counts, summed and of the first, and the
        sorted (token, expert) rows their grouped products visited; and of the sparse layers' prefix, where
        ``segment_attention`` reads it, the key blocks read and held."""
        counts = jnp.zeros((len(self.config.moe_layers()), self.config.num_experts), jnp.int32)
        aux = {"updates": jnp.zeros((), jnp.int32), "load": counts, "first_load": counts,
               "first_losses": jnp.zeros((3,), jnp.float32), "moe_rows_run": jnp.zeros((), jnp.int32)}
        if self.ragged_prefix:
            aux["segment_blocks"] = jnp.zeros((2,), jnp.int32)
        return aux

    def rows_run(self, load, tokens: int) -> jax.Array:
        """Sorted rows the expert layers' grouped products visited in one update of ``tokens`` tokens, from its
        router's counts ``load`` (expert layers, E): ``decoder.rows_run``, summed over the layers."""
        return jnp.sum(decoder.rows_run(load, tokens * self.config.num_experts_per_tok, self.config))

    def after_update(self, p, load):
        """The experts' selection bias follows the router's counts of the update."""
        return {**p, "params": decoder.update_router_bias(p["params"], load, self.config)}

    def rollout_stats(self, rollout, init_carry, venv, actor) -> Dict[str, Any]:
        """The rollout as the caches produced it, with the observation that follows it (what a check against a
        full forward needs), how many of its steps lay past the window (none where no layer has one), and how many
        blocks of positions its ragged attention layers fetched."""
        pos, _ = decoder.segment_positions(rollout["is_first"][..., 0], init_carry["pos"])
        beyond = jnp.zeros((), jnp.int32) if self.window is None else jnp.sum(pos >= self.window)
        kept = ("actions", "logprobs", "values", "rewards", "dones", "is_first", "mask")
        stats = {
            **{k: rollout[k] for k in kept},
            "tokens": rollout[self.key], "next_tokens": venv.observe(actor["env"])[self.key],
            "next_is_first": actor["is_first"],
            "beyond_window": beyond, "steps": jnp.asarray(pos.size, jnp.int32), "cache_blocks": self.cache_blocks(pos),
        }
        if self.sparse:  # rows fetched of the caches, index keys scored, and the slots selected, step by step
            stats.update(sparse_rows=self.sparse * jnp.sum(jnp.minimum(pos + 1, self.config.index_topk)),
                         index_positions=self.sparse * jnp.sum(pos + 1), selected=rollout["selected"])
        return stats

    def cache_blocks(self, pos) -> jax.Array:
        """Blocks of positions the ragged attention layers fetch over decode steps at the positions ``pos``."""
        return sum(
            (jnp.sum(decode_attention.blocks_read(jnp.minimum(pos + 1, size))) for size in self.ragged_sizes),
            start=jnp.zeros((), jnp.int32))

    def cache_counts(self, steps: int, blocks: int, sparse_rows: int = 0) -> Dict[str, int]:
        """Positions ``steps`` decode steps fetched from the attention caches (whole blocks of a ragged layer, all
        of a plain one, the ``sparse_rows`` selected of a sparse one), and positions those caches held."""
        plain = self.cache_held - sum(self.ragged_sizes) - self.sparse * self.config.max_len
        return {"cache_read": blocks * decode_attention.BLOCK + steps * plain + sparse_rows, "cache_held": steps * self.cache_held}

    def host_counts(self, stats) -> Dict[str, Any]:
        first, held = self.config.experts_held
        routed = np.asarray(stats["load"])  # the router's counts of the dispatch's updates: `tokens x k` a pass over an expert layer
        load = routed[:, first:first + held]  # tokens per held expert
        steps = int(stats["steps"])
        sparse_rows = int(stats["sparse_rows"]) if self.sparse else 0
        counts = {"moe_load_max": load.max(), "moe_load_mean": load.mean(),
                  "beyond_window": np.asarray(stats["beyond_window"]), "steps": steps,
                  "carry_bytes": sum(self.carry_bytes.values()),
                  **self.cache_counts(steps, int(stats["cache_blocks"]), sparse_rows),
                  "moe_rows_run": int(stats["moe_rows_run"]), "moe_rows_all": int(routed.sum())}
        if self.sparse:  # the index keys the decode steps scored: every position each env's episode had written
            counts["index_bytes"] = int(stats["index_positions"]) * self.index_key_bytes
        if "segment_blocks" in stats:  # positions of the sparse layers' prefix the updates' kernels read, and held
            read, held = (int(x) * segment_attention.BLOCK for x in np.asarray(stats["segment_blocks"]))
            counts.update(segment_read=read, segment_held=held)
        if self.ssm_bytes:  # a model with state-space layers: every env step reads each layer's state and window and writes them
            counts["ssm_state_bytes"] = steps * 2 * self.ssm_bytes
        return counts


def build_decoder_agent(fabric: Any, cfg: Any, action_space: Any, max_len: int, agent_state: Optional[Any] = None):
    config = decoder.DecoderConfig.from_dict(dict(cfg.algo.decoder), vocab_size=int(action_space.n), max_len=max_len)
    agent = DecoderPPOAgent(config, tuple(cfg.algo.mlp_keys.encoder), fabric.precision.compute_dtype)
    RECORDER.record("decoder.carry", layers=dict(Counter(config.layer_types)), bytes_per_env=agent.carry_bytes)
    if agent_state is not None:
        return agent, fabric.replicate(agent_state)
    return agent, jax.jit(agent.init, out_shardings=fabric.replicated)(jax.random.PRNGKey(cfg.seed))


def build_agent(
    fabric: Any,
    actions_dim: Sequence[int],
    is_continuous: bool,
    cfg: Any,
    obs_space: Any,
    agent_state: Optional[Any] = None,
) -> Tuple[RecurrentPPOAgent, Any]:
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    agent = RecurrentPPOAgent(
        actions_dim=tuple(actions_dim),
        is_continuous=is_continuous,
        mlp_keys=mlp_keys,
        encoder_units=cfg.algo.encoder.dense_units,
        mlp_layers=cfg.algo.mlp_layers,
        dense_act=cfg.algo.dense_act,
        layer_norm=cfg.algo.layer_norm,
        lstm_size=cfg.algo.rnn.lstm.hidden_size,
        pre_rnn=dict(cfg.algo.rnn.pre_rnn_mlp),
        post_rnn=dict(cfg.algo.rnn.post_rnn_mlp),
        actor_cfg=dict(cfg.algo.actor),
        critic_cfg=dict(cfg.algo.critic),
        dtype=fabric.precision.compute_dtype,
    )
    if agent_state is not None:
        return agent, fabric.replicate(agent_state)
    act_width = sum(actions_dim) if not is_continuous else int(sum(actions_dim))
    dummy_obs = {k: jnp.zeros((1, int(np.prod(obs_space[k].shape))), jnp.float32) for k in mlp_keys}
    params = agent.init(
        jax.random.PRNGKey(cfg.seed),
        method=RecurrentPPOAgent.step,
        carry=agent.initial_state(1),
        obs=dummy_obs,
        prev_actions=jnp.zeros((1, act_width), jnp.float32),
        is_first=jnp.ones((1, 1), jnp.float32),
    )
    return agent, fabric.replicate(params)


