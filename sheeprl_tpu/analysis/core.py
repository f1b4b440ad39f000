"""graftlint core: findings, suppressions, the statement-flow engine, and
the analyzer driver.

The analyzer is a pure-AST pass (no jax import, no code execution): every
rule receives a parsed :class:`SourceFile` plus the shared
:class:`RepoContext` (the composed-config key tree, the fault-site registry
extracted from ``resilience/faults.py``, the documented metric families) and
returns :class:`Finding` objects.  The driver applies suppression comments
and the checked-in baseline, then renders text/JSON reports.

Design constraints, in order:

1. **Zero unsuppressed findings on this repo** — rules prefer precision
   over recall; anything heuristic must either be fixable cheaply or
   baselinable with a reason.
2. **The two shipped bugs must be caught** — the PR 7 ``copy_to``
   zero-copy alias and the PR 14 donation-aliasing /
   ``device_put``-borrowed-buffer classes are regression fixtures in
   ``tests/test_analysis/``; any refactor of the donation rule must keep
   them red.
3. **Fast** — the whole-repo run is a CI stage with a <60 s wall budget
   and a tier-1 test; parsing ~350 files plus one YAML sweep fits in a few
   seconds.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import os
import re
import tokenize
from pathlib import Path
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Set, Tuple

REPO_PACKAGE = "sheeprl_tpu"

#: every rule id the engine knows, with a one-line meaning (the catalogue in
#: docs/static_analysis.md expands each with the historical bug it targets).
RULE_IDS: Dict[str, str] = {
    "use-after-donate": (
        "a variable passed in a donated argnum position of a compiled "
        "program (or an un-copied alias of one) is read after the dispatch"
    ),
    "donation-borrowed-buffer": (
        "a jax.device_put of a numpy value is passed in a donated argnum "
        "position — donation hands XLA a buffer it may not own"
    ),
    "trace-impure-time": (
        "host clock / host RNG call inside a traced function — the value "
        "freezes at trace time"
    ),
    "trace-host-concretize": (
        "float()/int()/bool()/np.* applied to a traced value inside a "
        "traced function — concretization error or silent host constant"
    ),
    "trace-python-branch": (
        "Python if/while/ternary on a traced value inside a traced "
        "function — per-value recompile or ConcretizationTypeError"
    ),
    "prng-key-reuse": (
        "a PRNG key is consumed by two sinks without an intervening "
        "jax.random.split / rebind"
    ),
    "prng-split-discarded": "the result of jax.random.split is discarded",
    "cfg-unknown-key": (
        "a cfg.<path> attribute access has no backing key anywhere in the "
        "composed sheeprl_tpu/configs/ tree"
    ),
    "cfg-dead-key": (
        "a YAML leaf under sheeprl_tpu/configs/ is read by no code path "
        "(dead config)"
    ),
    "fault-site-unknown": (
        "a fault-site string literal does not exist in "
        "resilience/faults.py's KNOWN_SITES registry"
    ),
    "metric-family-unknown": (
        "an emitted metric name uses a Family/ prefix that is not a "
        "documented metric family"
    ),
    "parse-error": "the file does not parse — nothing in it can be analyzed",
}


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str  # repo-relative, "/" separated
    line: int
    message: str
    context: str = ""  # enclosing function, yaml key path, ...

    def render(self) -> str:
        ctx = f" [{self.context}]" if self.context else ""
        return f"{self.path}:{self.line}: {self.rule}: {self.message}{ctx}"

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


class SourceFile:
    """One parsed Python file plus its suppression table."""

    def __init__(self, path: Path, rel: str, text: str):
        self.path = path
        self.rel = rel
        self.text = text
        self.tree = ast.parse(text, filename=str(path))
        self.lines = text.splitlines()
        self.suppressed_lines, self.suppressed_file, self.suppression_warnings = (
            _parse_suppressions(text)
        )

    def is_suppressed(self, rule: str, line: int) -> bool:
        if rule in self.suppressed_file or "all" in self.suppressed_file:
            return True
        rules = self.suppressed_lines.get(line)
        return bool(rules) and (rule in rules or "all" in rules)


_SUPPRESS_RE = re.compile(r"graftlint:\s*(disable(?:-file)?)\s*=\s*([\w,\- ]+)")


def _parse_suppressions(
    text: str,
) -> Tuple[Dict[int, Set[str]], Set[str], List[Tuple[int, Set[str]]]]:
    """``# graftlint: disable=<rule>[,<rule>...]`` suppresses the named
    rules on its own line; on a comment-only line it also covers the next
    code line.  ``# graftlint: disable-file=<rule>`` covers the whole file.
    Comments are read with tokenize so string literals can't fake one.
    Returns (per-line rules, file-wide rules, unknown-rule warnings) — a
    typo'd rule name suppresses nothing and is surfaced as a report note.
    """
    by_line: Dict[int, Set[str]] = {}
    file_wide: Set[str] = set()
    warnings: List[Tuple[int, Set[str]]] = []
    try:
        tokens = tokenize.generate_tokens(io.StringIO(text).readline)
        comments = [(t.start[0], t.string, t.line) for t in tokens if t.type == tokenize.COMMENT]
    except tokenize.TokenError:
        comments = []
    for lineno, comment, full_line in comments:
        m = _SUPPRESS_RE.search(comment)
        if not m:
            continue
        rules = {r.strip() for r in m.group(2).split(",") if r.strip()}
        unknown = rules - set(RULE_IDS) - {"all"}
        if unknown:
            rules -= unknown
            warnings.append((lineno, unknown))
        if "disable-file" in m.group(1):
            file_wide |= rules
        else:
            by_line.setdefault(lineno, set()).update(rules)
            if full_line.strip().startswith("#"):
                # comment-only line: also cover the next line
                by_line.setdefault(lineno + 1, set()).update(rules)
    return by_line, file_wide, warnings


# ---------------------------------------------------------------------------
# small AST helpers shared by the rules
# ---------------------------------------------------------------------------

def attr_chain(node: ast.AST) -> Optional[List[str]]:
    """``a.b.c`` -> ["a", "b", "c"]; None for anything non-dotted."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return parts[::-1]
    return None


def call_name(call: ast.Call) -> Optional[str]:
    """Last dotted segment of the callee (``fabric.compile`` -> "compile")."""
    func = call.func
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def literal_int_tuple(node: ast.AST) -> Optional[Tuple[int, ...]]:
    """A literal int or tuple/list of ints; None when not statically known."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int) and not isinstance(node.value, bool):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, int) and not isinstance(elt.value, bool):
                out.append(elt.value)
            else:
                return None
        return tuple(out)
    return None


def literal_str_tuple(node: ast.AST) -> Tuple[str, ...]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        return tuple(
            elt.value for elt in node.elts
            if isinstance(elt, ast.Constant) and isinstance(elt.value, str)
        )
    return ()


def assigned_names(stmt: ast.stmt) -> Set[str]:
    """Plain names (re)bound by this statement's assignment targets."""
    out: Set[str] = set()

    def collect(t: ast.AST) -> None:
        if isinstance(t, ast.Name):
            out.add(t.id)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                collect(e)
        elif isinstance(t, ast.Starred):
            collect(t.value)

    if isinstance(stmt, ast.Assign):
        for t in stmt.targets:
            collect(t)
    elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
        collect(stmt.target)
    elif isinstance(stmt, (ast.For, ast.AsyncFor)):
        collect(stmt.target)
    elif isinstance(stmt, (ast.With, ast.AsyncWith)):
        for item in stmt.items:
            if item.optional_vars is not None:
                collect(item.optional_vars)
    return out


# ---------------------------------------------------------------------------
# statement-flow engine
# ---------------------------------------------------------------------------

class FlowState:
    """Interface for the branch/loop-aware statement scan.

    Rules subclass this with their abstract state; :func:`flow_scan` drives
    it through a body in approximate execution order: If/Try branches are
    analyzed independently from a forked copy and merged; For/While bodies
    get TWO passes (so state created in iteration N is visible at the top of
    iteration N+1 — the shape of every "donated in the loop, read next
    iteration" bug); nested function/class definitions are handed to
    :meth:`on_nested_def` instead of being walked inline (their execution
    order is unknowable statically).
    """

    def fork(self) -> "FlowState":
        raise NotImplementedError

    def merge(self, *branches: "FlowState") -> None:
        raise NotImplementedError

    def visit(self, stmt: ast.stmt) -> None:
        raise NotImplementedError

    def on_nested_def(self, stmt: ast.stmt) -> None:  # noqa: B027 - optional hook
        pass


def _header_stmt(stmt: ast.stmt) -> List[ast.stmt]:
    """Synthetic statements covering ONLY a compound statement's header —
    the body is scanned separately, so visit() must never see it (it would
    process body reads/writes out of order)."""
    out: List[ast.stmt] = []

    def expr(e: ast.expr) -> ast.stmt:
        s = ast.Expr(value=e)
        ast.copy_location(s, e)
        return ast.fix_missing_locations(s)

    def assign(target: ast.expr, value: ast.expr) -> ast.stmt:
        s = ast.Assign(targets=[target], value=value)
        ast.copy_location(s, value)
        return ast.fix_missing_locations(s)

    if isinstance(stmt, (ast.For, ast.AsyncFor)):
        out.append(assign(stmt.target, stmt.iter))
    elif isinstance(stmt, ast.While):
        out.append(expr(stmt.test))
    elif isinstance(stmt, ast.If):
        out.append(expr(stmt.test))
    elif isinstance(stmt, (ast.With, ast.AsyncWith)):
        for item in stmt.items:
            if item.optional_vars is not None:
                out.append(assign(item.optional_vars, item.context_expr))
            else:
                out.append(expr(item.context_expr))
    return out


def flow_scan(body: Sequence[ast.stmt], state: FlowState) -> bool:
    """Scan ``body`` through ``state``.  Returns True when the body
    definitely TERMINATES the enclosing flow (return/raise/break/continue
    on every path) — a terminated branch's state is never merged back, so
    mutually-exclusive early-return paths can't cross-contaminate (the
    ``if continuous: return d.sample(key)`` / ``split(key)`` shape)."""
    for stmt in body:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            state.on_nested_def(stmt)
        elif isinstance(stmt, (ast.Return, ast.Raise, ast.Break, ast.Continue)):
            state.visit(stmt)  # reads in the return/raise expression count
            return True
        elif isinstance(stmt, ast.If):
            for h in _header_stmt(stmt):
                state.visit(h)
            s_body = state.fork()
            t_body = flow_scan(stmt.body, s_body)
            s_else = state.fork()
            t_else = flow_scan(stmt.orelse, s_else)
            live = [s for s, t in ((s_body, t_body), (s_else, t_else)) if not t]
            if live:
                state.merge(*live)
            if t_body and t_else:
                return True
        elif isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
            for h in _header_stmt(stmt):
                state.visit(h)
            for _ in range(2):
                s_loop = state.fork()
                flow_scan(stmt.body, s_loop)
                state.merge(s_loop)
            s_else = state.fork()
            flow_scan(stmt.orelse, s_else)
            state.merge(s_else)
        elif isinstance(stmt, ast.Try):
            s_body = state.fork()
            t_all = flow_scan(stmt.body, s_body)
            branches = [(s_body, t_all)]
            for handler in stmt.handlers:
                s_h = state.fork()
                branches.append((s_h, flow_scan(handler.body, s_h)))
            live = [s for s, t in branches if not t]
            if live:
                state.merge(*live)
            flow_scan(stmt.orelse, state)
            flow_scan(stmt.finalbody, state)
        elif isinstance(stmt, (ast.With, ast.AsyncWith)):
            for h in _header_stmt(stmt):
                state.visit(h)
            if flow_scan(stmt.body, state):
                return True
        else:
            state.visit(stmt)
    return False


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

class Report:
    def __init__(self) -> None:
        self.findings: List[Finding] = []
        self.suppressed: List[Finding] = []
        self.baselined: List[Finding] = []
        self.stale_baseline: List[Dict[str, Any]] = []
        self.notes: List[str] = []
        self.files_analyzed: int = 0
        self.wall_s: float = 0.0

    @property
    def unsuppressed(self) -> List[Finding]:
        return self.findings

    def counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for f in self.findings:
            out[f.rule] = out.get(f.rule, 0) + 1
        return out

    def to_dict(self) -> Dict[str, Any]:
        return {
            "files_analyzed": self.files_analyzed,
            "wall_s": round(self.wall_s, 3),
            "unsuppressed": [f.to_dict() for f in self.findings],
            "suppressed": len(self.suppressed),
            "baselined": len(self.baselined),
            "stale_baseline": self.stale_baseline,
            "counts": self.counts(),
            "notes": self.notes,
        }

    def render_text(self, verbose: bool = False) -> str:
        out: List[str] = []
        for f in self.findings:
            out.append(f.render())
        if verbose:
            for f in self.baselined:
                out.append(f"baselined: {f.render()}")
        for entry in self.stale_baseline:
            out.append(
                "stale baseline entry (matched nothing): "
                f"{entry.get('rule')} {entry.get('file', '*')} "
                f"match={entry.get('match', '')!r}"
            )
        for note in self.notes:
            out.append(f"note: {note}")
        out.append(
            f"graftlint: {len(self.findings)} unsuppressed finding(s), "
            f"{len(self.baselined)} baselined, {len(self.suppressed)} "
            f"comment-suppressed across {self.files_analyzed} file(s) "
            f"in {self.wall_s:.2f}s"
        )
        return "\n".join(out)


def iter_py_files(paths: Sequence[Path]) -> Iterable[Path]:
    seen: Set[Path] = set()
    for p in paths:
        if p.is_file() and p.suffix == ".py":
            if p not in seen:
                seen.add(p)
                yield p
        elif p.is_dir():
            for f in sorted(p.rglob("*.py")):
                if "__pycache__" in f.parts or f in seen:
                    continue
                seen.add(f)
                yield f


def repo_root() -> Path:
    """The repo checkout containing the installed package (parent of
    ``sheeprl_tpu/``)."""
    return Path(__file__).resolve().parents[2]


def relpath(path: Path, root: Path) -> str:
    try:
        return path.resolve().relative_to(root.resolve()).as_posix()
    except ValueError:
        return path.as_posix()


RuleFn = Callable[[SourceFile, Any], List[Finding]]


def run_analysis(
    paths: Optional[Sequence[os.PathLike]] = None,
    *,
    select: Optional[Sequence[str]] = None,
    baseline: Any = None,  # Baseline | None; resolved by caller/CLI
    context: Any = None,  # RepoContext; built lazily when None
    root: Optional[Path] = None,
) -> Report:
    """Analyze ``paths`` (default: the ``sheeprl_tpu`` package) and return a
    :class:`Report`.  This is the in-process entry the tier-1 test
    (``tests/test_analysis/test_repo_clean.py``) calls; the CLI wraps it."""
    import time as _time

    from sheeprl_tpu.analysis import donation, prng, purity, registry
    from sheeprl_tpu.analysis.context import RepoContext

    t0 = _time.perf_counter()
    root = root or repo_root()
    targets = [Path(p) for p in (paths or [root / REPO_PACKAGE])]
    ctx = context if context is not None else RepoContext.build(root)
    report = Report()
    report.notes.extend(ctx.notes)

    selected = set(select) if select else set(RULE_IDS)
    unknown = selected - set(RULE_IDS)
    if unknown:
        raise ValueError(f"unknown rule id(s): {sorted(unknown)}")

    per_file_rules: List[RuleFn] = [
        donation.check,
        purity.check,
        prng.check,
        registry.check_file,
    ]

    sources: List[SourceFile] = []
    raw: List[Finding] = []
    for path in iter_py_files(targets):
        rel = relpath(path, root)
        try:
            src = SourceFile(path, rel, path.read_text())
        except (SyntaxError, UnicodeDecodeError) as e:
            raw.append(Finding("parse-error", rel, getattr(e, "lineno", 1) or 1,
                               f"file does not parse: {e}"))
            continue
        sources.append(src)
        for rule in per_file_rules:
            raw.extend(rule(src, ctx))
    report.files_analyzed = len(sources)

    # repo-level rules (dead config; yaml-side fault sites) need the whole
    # read-set, so they run after the per-file sweep.  Dead config is only
    # meaningful when the WHOLE package was analyzed — on a file subset
    # every key the subset doesn't read would misreport as dead.
    pkg = (root / REPO_PACKAGE).resolve()
    full_package = any(Path(t).resolve() == pkg for t in targets)
    raw.extend(registry.check_repo(sources, ctx, dead_config=full_package))

    # dedupe (the loop two-pass produces repeats), stable order
    uniq: Dict[Tuple[str, str, int, str], Finding] = {}
    for f in raw:
        uniq.setdefault((f.rule, f.path, f.line, f.message), f)
    findings = sorted(uniq.values(), key=lambda f: (f.path, f.line, f.rule))

    by_rel = {s.rel: s for s in sources}
    for f in findings:
        src = by_rel.get(f.path)
        suppressed_inline = src is not None and src.is_suppressed(f.rule, f.line)
        # baseline matching runs even for DESELECTED rules so their ledger
        # entries register hits — otherwise `--select x --strict` would
        # falsely report every other rule's entries as stale
        baselined = (
            not suppressed_inline and baseline is not None and baseline.matches(f)
        )
        if f.rule not in selected:
            continue
        if suppressed_inline:
            report.suppressed.append(f)
        elif baselined:
            report.baselined.append(f)
        else:
            report.findings.append(f)
    for src in sources:
        for line, names in sorted(src.suppression_warnings):
            report.notes.append(
                f"{src.rel}:{line}: suppression comment names unknown rule(s) "
                f"{sorted(names)} — it suppresses nothing (see --list-rules)"
            )
    if baseline is not None:
        report.stale_baseline = baseline.stale_entries()
    report.wall_s = _time.perf_counter() - t0
    return report
