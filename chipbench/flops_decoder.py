"""Operations an update of the decoder PPO path needs, from the parameter shapes and the cell's sizes.

Counted per token of one forward pass (2 x multiply-adds): the five attention projections of
every layer, the two attention products at the mean context each layer kind sees under the
traffic's length distribution, the dense feed-forward or the router, the shared expert and the
routed experts' expected share held here (``k x held / experts`` experts a token), and the head.
A dispatch needs one forward for every token of the rollout and, for every epoch, a forward, a
backward (2 x forward) and the recomputed forward of the update (the layers are rematerialised:
without that the step does not fit, so the recomputation is part of what this cell's algorithm
needs on this chip); the total is spread over the dispatch's updates.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

Shapes = Dict[str, Tuple[int, ...]]


def mean_context(len_min: int, len_max: int, window: Optional[int] = None, grid: int = 4096) -> float:
    """Mean number of keys a decode step attends to, over all steps of episodes whose length is
    log-uniform on [len_min, len_max]: position p sees p + 1 keys, a window layer min(p + 1, window)."""
    total_keys = total_steps = 0.0
    lo, hi = math.log(len_min), math.log(len_max)
    for i in range(grid):
        length = math.exp(lo + (hi - lo) * (i + 0.5) / grid)
        if window is None or length <= window:
            keys = length * (length + 1) / 2
        else:
            keys = window * (window + 1) / 2 + (length - window) * window
        total_keys += keys
        total_steps += length
    return total_keys / total_steps


def _macs(shape: Tuple[int, ...]) -> float:
    out = 1.0
    for n in shape:
        out *= n
    return out


def forward_per_token(shapes: Shapes, model: Dict[str, Any], ctx_window: float, ctx_full: float) -> float:
    """2 x multiply-adds of one token's forward pass; ``shapes`` are the program's parameter shapes."""
    heads_width = model["num_attention_heads"] * model["head_dim"]
    first, held = model["experts_held"]
    total = 0.0
    for path, shape in shapes.items():
        if len(shape) < 2 or path.endswith("embed"):
            continue  # norms, the bias; the embedding is a lookup
        if "/moe/experts/" in path:  # (held, in, out): a token runs k of all the experts, held / experts of them here
            total += 2.0 * _macs(shape[1:]) * model["num_experts_per_tok"] * held / model["num_experts"]
        else:
            total += 2.0 * _macs(shape)
    for kind in model["layer_types"]:
        ctx = ctx_window if kind == "sliding_attention" else ctx_full
        total += 2.0 * 2.0 * heads_width * ctx  # scores and the weighted sum of values
    return total


def ppo_decoder(shapes: Shapes, model: Dict[str, Any], tokens: int, update_epochs: int, num_minibatches: int,
                len_min: int, len_max: int) -> float:
    """Per gradient update (see the module's note for what a dispatch needs)."""
    forward = forward_per_token(
        shapes, model, mean_context(len_min, len_max, model["sliding_window"]), mean_context(len_min, len_max)
    )
    per_dispatch = float(tokens) * forward * (1.0 + 4.0 * update_epochs)
    return per_dispatch / float(update_epochs * num_minibatches)
