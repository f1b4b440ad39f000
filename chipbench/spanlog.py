"""The program's own span log, as the per-layer readers of PR 28 take it.

Since PR 28 ``sheeprl_tpu.telemetry.SPANS`` keeps one record per closed span in memory
(``name, start, end`` on ``time.perf_counter``, the clock ``window.py`` is driven by, ``id``,
``parent``, ``iteration``, ``thread``, ``counts``), and the readers run in the program's process
after ``cli.run`` has unwound.  "Window" is the fenced window of a traced run
(``boundaries[0]`` to ``boundaries[-1]``); "stretch" is what follows it until the run ends,
unfenced, as users run.  Spans still open when the run was left are not in the log.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Tuple


def records() -> Optional[List[Any]]:
    """Every closed span, oldest first; None where the program keeps no span log (a checkout from
    before PR 28): the reader then returns nothing and the result line leaves its metric out."""
    try:
        from sheeprl_tpu.telemetry import SPANS

        return SPANS.records()
    except (ImportError, AttributeError):
        return None


def window_of(ctx: Dict[str, Any]) -> Tuple[float, float, int]:
    """(start, end, iterations) of the run's window."""
    bounds = ctx["window"].boundaries
    return bounds[0], bounds[-1], len(bounds) - 1


def in_window(log: Iterable[Any], names: Iterable[str], ctx: Dict[str, Any]) -> List[Any]:
    """Spans of these names that lie wholly inside the window."""
    t0, t1, _ = window_of(ctx)
    names = frozenset(names)
    return [r for r in log if r.name in names and r.start >= t0 and r.end <= t1]


def in_stretch(log: Iterable[Any], names: Iterable[str], ctx: Dict[str, Any]) -> List[Any]:
    """Spans of these names that began after the window had closed."""
    _, t1, _ = window_of(ctx)
    names = frozenset(names)
    return [r for r in log if r.name in names and r.start >= t1]


def ms(record: Any) -> float:
    return (record.end - record.start) * 1e3
