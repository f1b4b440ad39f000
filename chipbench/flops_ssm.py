"""Operations an update of the decoder PPO path needs where a layer is ONE part: a Mamba-2 state-space mixer,
an attention mixer, or a sparse feed-forward (``layer_types`` holds ``mamba2`` and ``moe`` beside
``full_attention``).

Counted per token of one forward pass (2 x multiply-adds), from the program's parameter shapes as
``flops_decoder`` counts them: every matrix by its shape (a Mamba-2 layer's in and out projections and its
``(K, channels)`` taps as ``K`` multiply-adds a channel, an attention layer's four, the router, the shared
expert), the routed experts' expected share held here (``k x held / experts`` experts a token), and the head.
What the shapes do not give: the two attention products at the mean context the traffic's length distribution
gives, for the attention layers alone, and per Mamba-2 layer the state's update and its read, a multiply-add
each an entry of the ``heads x head_dim x state`` state (``4 x heads x head_dim x state``: what the recurrence
needs of a token, however the program chunks it).  A dispatch needs one forward for every token of the rollout
and, for every epoch, a forward, a backward (2 x forward) and the recomputed forward of the update, as
``flops_decoder.ppo_decoder`` says and why.
"""

from __future__ import annotations

from typing import Any, Dict

from chipbench import flops_decoder
from chipbench.flops_decoder import Shapes, mean_context

ATTENTION = ("full_attention", "sliding_attention")
MAMBA = "mamba2"


def state_per_token(model: Dict[str, Any]) -> float:
    """2 x multiply-adds of one token through one Mamba-2 layer's state: the update and the read."""
    return 4.0 * model["ssm_heads"] * model["ssm_head_dim"] * model["ssm_state_size"]


def forward_per_token(shapes: Shapes, model: Dict[str, Any], ctx_window: float, ctx_full: float) -> float:
    """2 x multiply-adds of one token's forward pass; ``shapes`` are the program's parameter shapes.
    ``flops_decoder`` counts every matrix by its shape and the attention products of the layers it is told of:
    told of the attention layers alone, it leaves each Mamba-2 layer's state."""
    attention = [kind for kind in model["layer_types"] if kind in ATTENTION]
    states = sum(state_per_token(model) for kind in model["layer_types"] if kind == MAMBA)
    return flops_decoder.forward_per_token(shapes, dict(model, layer_types=attention), ctx_window, ctx_full) + states


def ppo_ssm(shapes: Shapes, model: Dict[str, Any], tokens: int, update_epochs: int, num_minibatches: int,
            len_min: int, len_max: int) -> float:
    """Per gradient update (see ``flops_decoder``'s note for what a dispatch needs)."""
    window = model.get("sliding_window") or None
    forward = forward_per_token(
        shapes, model, mean_context(len_min, len_max, window) if window else 0.0, mean_context(len_min, len_max)
    )
    per_dispatch = float(tokens) * forward * (1.0 + 4.0 * update_epochs)
    return per_dispatch / float(update_epochs * num_minibatches)
