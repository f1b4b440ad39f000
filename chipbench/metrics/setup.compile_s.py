"""Seconds of compiling before the window, by the program's own span log (s): the union of the ``compile.trace``,
``compile.lower`` and ``compile.backend`` records on the loop's thread from ``setup``'s start to the window's opening,
wherever they fell: inside ``setup``, or in the first iterations, where the steady program is first dispatched.  The
records nest (an inner ``jit`` traced inside an outer one; a backend read inside an ``exec.*`` span), hence the union.

``account`` is the whole of set-up in six parts that add up to the run's ``setup_s``; the other ``setup.*`` readers
take their part from it.  Nothing where the log holds no ``setup`` record (a checkout from before PR 38, or a log
that has wrapped): no reader guesses."""

from chipbench import spanlog


def union_s(intervals):
    """Seconds covered by ``(start, end)`` intervals, overlaps counted once."""
    covered, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > reach:
            covered += end - max(start, reach)
            reach = end
    return covered


def setup_root(log, ctx):
    """The newest ``setup`` record that closed before the window opened (the run's own), and that opening."""
    t_open = ctx["window"].boundaries[0]
    roots = [r for r in log if r.name == "setup" and r.end <= t_open]
    return (roots[-1] if roots else None), t_open


def account(ctx):
    log = spanlog.records()
    if log is None:
        return None
    root, t_open = setup_root(log, ctx)
    if root is None:
        return None
    compiles = [(r.start, r.end) for r in log if r.name.startswith("compile.") and r.thread == root.thread]

    def compile_in(t0, t1):
        return union_s((max(s, t0), min(e, t1)) for s, e in compiles if e > t0 and s < t1)

    def less_compile(spans):
        return sum(r.end - r.start - compile_in(r.start, r.end) for r in spans)

    children = [r for r in log if r.parent == root.id and r.name.startswith("setup.")]
    prefill = [r for r in children if r.name == "setup.prefill"]
    own = less_compile([root]) - less_compile(children)  # the children follow one another on the root's thread
    pre_run_ms = (root.counts or {}).get("pre_run_ms")  # left out where the platform gave no start time
    return {
        "pre_run_s": None if pre_run_ms is None else pre_run_ms / 1e3,
        "compile_s": compile_in(root.start, t_open),
        "build_s": less_compile([r for r in children if r.name != "setup.prefill"]),
        "prefill_s": less_compile(prefill),
        "warmup_s": t_open - root.end - compile_in(root.end, t_open),
        "untracked_s": max(own, 0.0),
    }


def read(ctx):
    parts = account(ctx)
    return None if parts is None else parts["compile_s"]
