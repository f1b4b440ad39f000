"""Share of the backend compiles before the window that the persistent cache answered (%): 100 x the sum of the count
``cache_hit`` over the number of ``compile.backend`` records, on any thread, from ``setup``'s start to the window's
opening.  Near 0: the run set up cold and ``setup.compile_s`` is the price; near 100: warm.  Nothing where the log
holds no ``setup`` record or no backend compile."""

from chipbench import spanlog
from chipbench.harness import load_module


def read(ctx):
    log = spanlog.records()
    if log is None:
        return None
    root, t_open = load_module("metrics", "setup.compile_s").setup_root(log, ctx)
    if root is None:
        return None
    hits = [(r.counts or {}).get("cache_hit", 0) for r in log if r.name == "compile.backend" and r.start >= root.start and r.end <= t_open]
    return 100.0 * sum(hits) / len(hits) if hits else None
