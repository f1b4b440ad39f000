"""The arithmetic that decides ``correct``: gaps between what the program made and the reference.

By the worst leaf: the gap between the program's norm and the reference's norm of a leaf
(not the norm of their difference), measured against the reference's norm of that leaf or
of the median leaf, whichever is larger, since some gradients are all but zero.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np


def _leaves(tree: Any, prefix: str = "") -> Dict[str, np.ndarray]:
    if isinstance(tree, dict):
        out: Dict[str, np.ndarray] = {}
        for k in sorted(tree):
            out.update(_leaves(tree[k], f"{prefix}/{k}" if prefix else str(k)))
        return out
    if isinstance(tree, (list, tuple)):
        out = {}
        for i, v in enumerate(tree):
            out.update(_leaves(v, f"{prefix}/{i}" if prefix else str(i)))
        return out
    return {prefix: np.asarray(tree, np.float64)}


def leaf_norms(tree: Any) -> Dict[str, float]:
    return {k: float(np.sqrt(np.sum(v * v))) for k, v in _leaves(tree).items()}


def tree_sub(a: Any, b: Any) -> Dict[str, np.ndarray]:
    la, lb = _leaves(a), _leaves(b)
    return {k: la[k] - lb[k] for k in la}


def adam_state(opt_state: Any) -> Dict[str, Any]:
    """mu, nu and the count out of an optax chain's state, wherever the chain keeps them."""
    found: Dict[str, Any] = {}

    def walk(node: Any) -> None:
        if hasattr(node, "mu") and hasattr(node, "nu"):
            found.update(mu=node.mu, nu=node.nu, count=node.count)
        elif hasattr(node, "inner_state"):
            walk(node.inner_state)
        elif isinstance(node, (tuple, list)):
            for child in node:
                walk(child)

    walk(opt_state)
    if not found:
        raise ValueError("no Adam state in the optimizer state")
    return found


def change_norms(after: Any, before: Any) -> Dict[str, float]:
    """Per leaf, the norm of ``after - before``."""
    return {k: float(np.sqrt(np.sum(v * v))) for k, v in tree_sub(after, before).items()}


def leaf_gaps(
    program: Dict[str, float], reference: Dict[str, float], skip: Optional[List[str]] = None
) -> Dict[str, float]:
    """Per leaf: |norm_p - norm_r| / max(norm_r, median leaf's norm_r)."""
    names = [k for k in reference if not skip or k not in skip]
    med = float(np.median([reference[k] for k in names]))
    out = {}
    for k in names:
        denom = max(reference[k], med)
        gap = abs(program[k] - reference[k]) / denom if denom > 0 else float(program[k] != 0)
        out[k] = gap if np.isfinite(gap) else float("inf")
    return out


def leaf_diffs(program: Any, reference: Any, skip: Optional[List[str]] = None) -> Dict[str, float]:
    """Per leaf: the norm of the difference, |p - r| / max(|r|, median leaf's |r|).  First order in what
    ``leaf_gaps`` sees at second order: a change of direction that keeps the length (another sample of the
    same gradient noise) reads e here and e^2 / 2 there."""
    lp, lr = _leaves(program), _leaves(reference)
    names = [k for k in lr if not skip or k not in skip]
    norm = {k: float(np.sqrt(np.sum(lr[k] ** 2))) for k in names}
    med = float(np.median(list(norm.values())))
    out = {}
    for k in names:
        denom = max(norm[k], med)
        diff = float(np.sqrt(np.sum((lp[k] - lr[k]) ** 2)))
        gap = diff / denom if denom > 0 else float(diff != 0)
        out[k] = gap if np.isfinite(gap) else float("inf")
    return out


def worst_few(gaps: Dict[str, float], n: int = 6) -> Dict[str, float]:
    """The ``n`` leaves that read most, for the run's own record of where a gap sits."""
    return dict(sorted(gaps.items(), key=lambda kv: -kv[1])[:n])


def tiny_gradient_leaves(grad_norms: Dict[str, float], share: float = 1e-3) -> List[str]:
    """Leaves whose gradient is nought to rounding in the reference (under ``share`` of the
    median leaf's): Adam moves them by round-off alone, so their change is not compared."""
    med = float(np.median(list(grad_norms.values())))
    return [k for k, v in grad_norms.items() if v < share * med]


def scalar_gap(program: float, reference: float, floor: float) -> float:
    gap = abs(float(program) - float(reference)) / max(abs(float(reference)), floor)
    return gap if np.isfinite(gap) else float("inf")
