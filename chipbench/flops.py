"""Operations an update needs, from the configuration's shapes alone.

The functions count what the algorithm requires (forward = 2 x multiply-adds of every
kernel, training = 3 x forward), not what the compiled program happens to execute, so the
number cannot change when the program does.  ``dv3`` is a copy of ``bench.py``
``_dv3_analytic_flops`` (PR 23); ``bench.py`` is no longer its home of record.  One
correction to the copy: a stride-2 transposed convolution needs one multiply-add per tap
and *input* position (four per output position, not sixteen), so ``deconv_i`` is weighted
by its input grid; the original counts the zeros a zero-insertion lowering multiplies by,
which put DV3-S's step at 1.25 TFLOP an update where the algorithm needs 0.76.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Tuple

Shapes = Dict[str, Tuple[int, ...]]  # "/"-joined parameter path -> shape


def kernel_forward_flops(shapes: Shapes, image_hw: int = 64, conv_padding: str = "SAME") -> float:
    """2 x multiply-adds of one forward pass over one 64x64 frame (or one latent token).

    A convolution kernel is weighted by its output positions in the stride-2 pyramid (conv_i
    at (hw / 2^(i+1))^2), a transposed one by its input positions (deconv_i at (4 * 2^i)^2,
    from a 4x4 grid; the last at (hw / 2)^2); dense kernels count once.
    """
    total = 0.0
    for path, shape in shapes.items():
        if len(shape) < 2:
            continue
        macs = 1.0
        for n in shape:
            macs *= n
        m = re.search(r"(de)?conv_(\d+)|deconv_out", path)
        if m and len(shape) == 4:
            if "deconv_out" in path:
                positions = (image_hw // 2) ** 2
            elif m.group(1):
                positions = (4 * 2 ** int(m.group(2))) ** 2
            else:
                positions = (image_hw // 2 ** (int(m.group(2)) + 1)) ** 2
            macs *= positions
        total += 2.0 * macs
    return total


def _under(shapes: Shapes, *prefixes: str) -> Shapes:
    return {k: v for k, v in shapes.items() if any(k.startswith(p) for p in prefixes)}


def dv3(shapes: Shapes, batch: int, seq_len: int, horizon: int) -> float:
    """DreamerV3, per gradient update: the world model trains on B*L tokens (3x); imagination
    rolls the dynamics forward-only (1x) and trains the actor (3x) for ``horizon`` steps from
    B*L starts; critic (3x) and target critic (1x) read horizon+1 imagined states."""
    tokens = float(batch * seq_len)
    wm = kernel_forward_flops(_under(shapes, "world_model/"))
    actor = kernel_forward_flops(_under(shapes, "actor/"))
    critic = kernel_forward_flops(_under(shapes, "critic/"))
    target = kernel_forward_flops(_under(shapes, "target_critic/"))
    dyn = kernel_forward_flops(
        _under(shapes, "world_model/params/recurrent_model/", "world_model/params/transition_model/")
    )
    return (
        3.0 * tokens * wm
        + tokens * horizon * (dyn + 3.0 * actor)
        + tokens * (horizon + 1) * (3.0 * critic + target)
    )


def ppo_fused(shapes: Shapes, num_envs: int, rollout_steps: int, update_epochs: int, num_minibatches: int) -> float:
    """Fused rollout+update PPO, per gradient update: every frame of the rollout needs one
    policy/value forward when it is collected (1x) and one training pass per epoch (3x); the
    dispatch's total is spread over its epochs x minibatches updates.  The program's second
    forward on the final observation (used on truncated rows only) and its recomputation of the
    values for GAE are not counted: the algorithm does not need them."""
    frames = float(num_envs * rollout_steps)
    forward = kernel_forward_flops(shapes)
    per_dispatch = frames * forward * (1.0 + 3.0 * update_epochs)
    return per_dispatch / float(update_epochs * num_minibatches)


def shapes_of(tree: Any, prefix: str = "") -> Shapes:
    """"/"-joined paths -> shapes of a nested dict of arrays."""
    out: Shapes = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            out.update(shapes_of(v, f"{prefix}/{k}" if prefix else str(k)))
    elif hasattr(tree, "shape"):
        out[prefix] = tuple(int(n) for n in tree.shape)
    return out
