"""The measured window: iteration boundaries in, whole-window rates and the p90 out.

Pure arithmetic on a clock that is handed in, so ``tests/test_window.py`` drives it with
a fake one.  A rate is all the work of the window over all its fenced time; the p90 is
over every iteration of the window.
"""

from __future__ import annotations

import statistics
from typing import Callable, Dict, List, Optional


class Window:
    """Counts work between ``open()`` and the first boundary at or after ``seconds``."""

    def __init__(self, seconds: float, clock: Callable[[], float]):
        self.seconds = float(seconds)
        self.clock = clock
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None
        self.boundaries: List[float] = []
        self.env_steps = 0
        self.updates = 0

    @property
    def is_open(self) -> bool:
        return self.t_open is not None and self.t_close is None

    def open(self) -> None:
        self.t_open = self.clock()
        self.boundaries = [self.t_open]
        self.env_steps = self.updates = 0

    def add(self, env_steps: int = 0, updates: int = 0) -> None:
        if self.is_open:
            self.env_steps += int(env_steps)
            self.updates += int(updates)

    def boundary(self) -> bool:
        """An iteration ended.  True when the window is due to close (the caller fences, then ``close()``)."""
        now = self.clock()
        self.boundaries.append(now)
        return now - self.t_open >= self.seconds

    def close(self) -> None:
        """Called after the closing fence: the last iteration ends when the device has finished it."""
        self.t_close = self.clock()
        self.boundaries[-1] = self.t_close

    # -- what comes out ----------------------------------------------------
    @property
    def elapsed(self) -> float:
        return self.t_close - self.t_open

    def iteration_ms(self) -> List[float]:
        b = self.boundaries
        return [(b[i + 1] - b[i]) * 1e3 for i in range(len(b) - 1)]

    def metrics(self) -> Dict[str, float]:
        its = self.iteration_ms()
        return {
            "env_steps_per_s": self.env_steps / self.elapsed,
            "updates_per_s": self.updates / self.elapsed,
            "iter_p90_ms": percentile(its, 90.0),
        }


def percentile(values: List[float], pct: float) -> float:
    """Nearest-rank percentile (no interpolation: it is one of the iterations)."""
    if not values:
        raise ValueError("no iteration ended inside the window")
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * pct // 100))  # ceil
    return ordered[int(rank) - 1]


def median(values: List[float]) -> Optional[float]:
    return statistics.median(values) if values else None
