"""Operations an update of the decoder PPO path needs where the attention layers read the keys a learned indexer
selects (``layer_types`` holds ``sparse_attention``).

Counted per token of one forward pass (2 x multiply-adds), from the program's parameter shapes as ``flops_decoder``
counts them: every matrix by its shape (an attention layer's four, the indexer's three, the router), the routed
experts' expected share held here (``k x held / experts`` experts a token), and the head.  What the shapes do not
give, at the mean the traffic's length distribution gives: per sparse layer the index scores over every position
the episode has written (``p + 1`` keys at position ``p``: a dot product of ``index_head_dim`` a head, then the head's
weight on its ``relu``, a multiply-add a head), and the two attention products over the ``min(p + 1, index_topk)``
selected keys (the algorithm's work; a masked product over every key does more).  The update besides needs L_I a
token and sparse layer: the main attention's probabilities summed over its heads, and the softmax and the divergence
over the selected keys (``heads + 4`` operations a key).  A dispatch needs one forward for every token of the rollout
and, for every epoch, a forward, a backward (2 x forward) and the recomputed forward of the update, as
``flops_decoder.ppo_decoder`` says and why, L_I in the same four passes.
"""

from __future__ import annotations

from typing import Any, Dict

from chipbench import flops_decoder
from chipbench.flops_decoder import Shapes, mean_context

SPARSE = "sparse_attention"


def sparse_per_token(model: Dict[str, Any], ctx_index: float, ctx_selected: float) -> float:
    """2 x multiply-adds of one token through one sparse layer beyond its matrices: the index scores over
    ``ctx_index`` keys and the attention products over ``ctx_selected``."""
    heads_width = model["num_attention_heads"] * model["head_dim"]
    index = 2.0 * model["index_heads"] * (model["index_head_dim"] + 1) * ctx_index
    return index + 2.0 * 2.0 * heads_width * ctx_selected


def index_loss_per_token(model: Dict[str, Any], ctx_selected: float) -> float:
    """Operations of L_I of one token and one sparse layer."""
    return (model["num_attention_heads"] + 4.0) * ctx_selected


def forward_per_token(shapes: Shapes, model: Dict[str, Any], ctx_index: float, ctx_selected: float) -> float:
    """2 x multiply-adds of one token's forward pass; ``shapes`` are the program's parameter shapes.  ``flops_decoder``
    counts every matrix by its shape and the attention products of the layers it is told of: told of none, it leaves
    the sparse layers' products to ``sparse_per_token``."""
    layers = sum(1 for kind in model["layer_types"] if kind == SPARSE)
    others = [kind for kind in model["layer_types"] if kind != SPARSE]
    return (flops_decoder.forward_per_token(shapes, dict(model, layer_types=others), 0.0, 0.0)
            + layers * sparse_per_token(model, ctx_index, ctx_selected))


def ppo_sparse(shapes: Shapes, model: Dict[str, Any], tokens: int, update_epochs: int, num_minibatches: int,
               len_min: int, len_max: int) -> float:
    """Per gradient update (see the module's note for what a dispatch needs)."""
    ctx_index = mean_context(len_min, len_max)
    ctx_selected = mean_context(len_min, len_max, model["index_topk"])
    forward = forward_per_token(shapes, model, ctx_index, ctx_selected)
    index_loss = sum(index_loss_per_token(model, ctx_selected) for kind in model["layer_types"] if kind == SPARSE)
    per_dispatch = float(tokens) * (forward + 4.0 * update_epochs * (forward + index_loss))
    return per_dispatch / float(update_epochs * num_minibatches)
