"""Operations an update of the decoder PPO path needs where only some layers attend: a hybrid of gated
short convolutions and attention (``layer_types`` holds ``conv`` beside ``full_attention``).

Counted per token of one forward pass (2 x multiply-adds), from the program's parameter shapes as
``flops_decoder`` counts them: every projection by its shape (a conv layer's in and out projections, an
attention layer's four, the dense feed-forward or the router), the routed experts' expected share held
here (``k x held / experts`` experts a token), and the head.  What the shapes do not give: the two
attention products at the mean context the traffic's length distribution gives, for the attention
layers alone, and a conv layer's two gates (``B * u`` and ``C * c``: one multiply each a channel; its
``L`` taps a channel are its ``conv_w`` leaf, counted by shape).  A dispatch needs one forward for every
token of the rollout and, for every epoch, a forward, a backward (2 x forward) and the recomputed
forward of the update, as ``flops_decoder.ppo_decoder`` says and why.
"""

from __future__ import annotations

from typing import Any, Dict

from chipbench import flops_decoder
from chipbench.flops_decoder import Shapes, mean_context

ATTENTION = ("full_attention", "sliding_attention")


def forward_per_token(shapes: Shapes, model: Dict[str, Any], ctx_window: float, ctx_full: float) -> float:
    """2 x multiply-adds of one token's forward pass; ``shapes`` are the program's parameter shapes.
    ``flops_decoder`` counts every matrix by its shape (a conv layer's projections, and its ``(L, H)`` taps as
    ``L`` multiply-adds a channel) and the attention products of the layers it is told of: told of the
    attention layers alone, it leaves the conv layers' two gates, a multiply each a channel."""
    attention = [kind for kind in model["layer_types"] if kind in ATTENTION]
    gates = 2.0 * model["hidden_size"] * (len(model["layer_types"]) - len(attention))
    return flops_decoder.forward_per_token(shapes, dict(model, layer_types=attention), ctx_window, ctx_full) + gates


def ppo_hybrid(shapes: Shapes, model: Dict[str, Any], tokens: int, update_epochs: int, num_minibatches: int,
               len_min: int, len_max: int) -> float:
    """Per gradient update (see ``flops_decoder``'s note for what a dispatch needs)."""
    window = model.get("sliding_window") or None
    forward = forward_per_token(
        shapes, model, mean_context(len_min, len_max, window) if window else 0.0, mean_context(len_min, len_max)
    )
    per_dispatch = float(tokens) * forward * (1.0 + 4.0 * update_epochs)
    return per_dispatch / float(update_epochs * num_minibatches)
