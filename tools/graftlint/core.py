"""graftlint engine: file loading, suppressions, rule registry, runner.

Design constraints (tools/graftlint/__init__.py has the why):

  - PURE AST: scanned files are parsed, never imported — a lint run can
    not trigger a jax platform init, a TF import, or module-level side
    effects, and a file that fails to import (missing optional dep)
    still gets linted.
  - One parse per file: every rule sees the same `FileContext` (source,
    AST, suppression table), so the whole suite is one O(files) walk.
  - Findings are baseline-matched WITHOUT line numbers (rule + path +
    symbol + message): editing an unrelated part of a file must not
    resurrect a grandfathered finding.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import os
import re
import tokenize
from typing import Dict, Iterable, List, Optional, Sequence, Set

# repo root = the directory holding tools/ (pytest.ini, config, README
# all resolve relative to it); rules that need repo-level files take an
# explicit root so fixtures can point them elsewhere.
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the tier-1 scan set (ROADMAP tier-1 runs the suite over exactly this)
DEFAULT_PATHS = ("code2vec_tpu", "tools", "tests")

# never scanned: bytecode, native build trees, and the lint fixtures
# (deliberate true positives — scanning them would fail the repo run)
EXCLUDE_DIRS = frozenset({"__pycache__", "graftlint_fixtures", "build",
                          ".git", ".claude"})

_SUPPRESS_RE = re.compile(
    r"#\s*graftlint:\s*disable(?P<file>-file)?=(?P<rules>[\w,-]+)")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One lint finding. `symbol` is the enclosing def/class qualname
    (baseline stability: line numbers shift, symbols rarely do).
    `detail` is context that may legitimately change when UNRELATED
    code moves (e.g. which hot root first reached a function — BFS
    order); it is rendered but kept OUT of the baseline identity, so
    such drift cannot invalidate grandfathered entries."""

    rule: str
    path: str      # repo-root-relative, posix separators
    line: int
    message: str
    symbol: str = ""
    detail: str = ""

    def key(self) -> tuple:
        """Baseline identity — deliberately line- and detail-free."""
        return (self.rule, self.path, self.symbol, self.message)

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    def render(self) -> str:
        sym = f" [{self.symbol}]" if self.symbol else ""
        det = f" ({self.detail})" if self.detail else ""
        return (f"{self.path}:{self.line}: {self.rule}{sym}: "
                f"{self.message}{det}")


class FileContext:
    """One parsed source file: AST + the suppression table.

    A `# graftlint: disable=<rules>` comment suppresses matching
    findings on its OWN line and on the NEXT line (so it can trail the
    offending statement or sit on its own line above it);
    `disable-file=` suppresses for the whole file. Rule name `all`
    matches every rule.
    """

    def __init__(self, path: str, root: str = REPO_ROOT):
        self.path = os.path.abspath(path)
        self.root = root
        self.rel = os.path.relpath(self.path, root).replace(os.sep, "/")
        with open(self.path, "r", encoding="utf-8",
                  errors="replace") as f:
            self.source = f.read()
        self.tree = ast.parse(self.source, filename=self.path)
        self.line_suppressed: Dict[int, Set[str]] = {}
        self.file_suppressed: Set[str] = set()
        self._collect_suppressions()

    def _collect_suppressions(self) -> None:
        try:
            tokens = tokenize.generate_tokens(
                io.StringIO(self.source).readline)
            comments = [(t.start[0], t.string) for t in tokens
                        if t.type == tokenize.COMMENT]
        except tokenize.TokenError:
            comments = []
        for line, text in comments:
            m = _SUPPRESS_RE.search(text)
            if not m:
                continue
            rules = {r.strip() for r in m.group("rules").split(",")
                     if r.strip()}
            if m.group("file"):
                self.file_suppressed |= rules
            else:
                for ln in (line, line + 1):
                    self.line_suppressed.setdefault(ln, set()).update(rules)

    def suppressed(self, rule: str, line: int) -> bool:
        for pool in (self.file_suppressed,
                     self.line_suppressed.get(line, ())):
            if rule in pool or "all" in pool:
                return True
        return False


class Rule:
    """One named check. Per-file rules implement `check_file`; rules
    needing the whole scan set (call graphs, cross-file consistency)
    implement `check_repo`; rules consuming the shared function index /
    call graph / summaries (ISSUE 14) implement `check_scan`. A rule
    may implement any combination."""

    name: str = ""
    description: str = ""

    def check_file(self, ctx: FileContext) -> Iterable[Finding]:
        return ()

    def check_repo(self, ctxs: Sequence[FileContext],
                   root: str) -> Iterable[Finding]:
        return ()

    def check_scan(self, scan: "Scan") -> Iterable[Finding]:
        return ()


_REGISTRY: Dict[str, Rule] = {}


def register(rule_cls):
    """Class decorator: instantiate + register a Rule by its name."""
    rule = rule_cls()
    assert rule.name and rule.name not in _REGISTRY, rule_cls
    _REGISTRY[rule.name] = rule
    return rule_cls


def _load_rules() -> None:
    if _REGISTRY:
        return
    # importing the package registers every rule module
    import tools.graftlint.rules  # noqa: F401


def all_rules() -> Dict[str, Rule]:
    _load_rules()
    return dict(_REGISTRY)


def get_rule(name: str) -> Rule:
    _load_rules()
    return _REGISTRY[name]


def iter_py_files(paths: Sequence[str], root: str) -> List[str]:
    """Expand files/dirs into a sorted .py file list (excludes
    EXCLUDE_DIRS at any depth)."""
    out: List[str] = []
    for p in paths:
        p = p if os.path.isabs(p) else os.path.join(root, p)
        if os.path.isfile(p):
            out.append(p)
            continue
        if not os.path.isdir(p):
            # a typo'd path silently scanning zero files would report
            # "clean" (and mark the whole baseline stale) — fail loud
            raise FileNotFoundError(f"graftlint: no such path: {p}")
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = sorted(d for d in dirnames
                                 if d not in EXCLUDE_DIRS)
            out.extend(os.path.join(dirpath, f)
                       for f in sorted(filenames) if f.endswith(".py"))
    return out


def run_lint(paths: Sequence[str] = DEFAULT_PATHS,
             root: str = REPO_ROOT,
             rules: Optional[Sequence[str]] = None,
             ambiguous_names: frozenset = frozenset()) -> List[Finding]:
    """Parse every file once, run the selected rules, apply inline
    suppressions, return findings sorted by (path, line, rule).
    Baseline filtering is the caller's concern (tools/graftlint/
    baseline.py) — this returns EVERYTHING the rules see.
    `ambiguous_names` (subset scans — the `--changed` gate) blocks
    uniqueness resolution for names the FULL scan set defines more
    than once (CallGraph docstring)."""
    _load_rules()
    selected = [_REGISTRY[r] for r in rules] if rules \
        else list(_REGISTRY.values())
    ctxs: List[FileContext] = []
    findings: List[Finding] = []
    for path in iter_py_files(paths, root):
        try:
            ctxs.append(FileContext(path, root))
        except SyntaxError as e:
            findings.append(Finding(
                rule="parse-error",
                path=os.path.relpath(path, root).replace(os.sep, "/"),
                line=e.lineno or 0,
                message=f"file does not parse: {e.msg}"))
    by_rel = {c.rel: c for c in ctxs}
    scan = Scan(ctxs, root, ambiguous_names)
    for rule in selected:
        for ctx in ctxs:
            findings.extend(rule.check_file(ctx))
        findings.extend(rule.check_repo(ctxs, root))
        findings.extend(rule.check_scan(scan))
    kept = []
    for f in findings:
        ctx = by_rel.get(f.path)
        if ctx is not None and ctx.suppressed(f.rule, f.line):
            continue
        kept.append(f)
    kept.sort(key=lambda f: (f.path, f.line, f.rule, f.message))
    return kept


# ---- the shared repo view: function index + heuristic call graph ----
#
# Moved here from rules/host_sync.py (ISSUE 14): the summary layer and
# both new rule families need the same index and the same name-heuristic
# resolution, and computing them once per run is what keeps the
# two-pass scan inside the tier-1 wall bound.

@dataclasses.dataclass
class FnInfo:
    """One function definition in the scan set."""
    ctx: FileContext
    node: ast.AST           # FunctionDef / AsyncFunctionDef
    cls: str                # enclosing class name ('' at module level)
    scope: str = ""         # enclosing DEF chain ('' unless nested in
    #                         a function: 'outer' / 'outer.inner') —
    #                         keeps a nested def from colliding with a
    #                         same-named module-level def in key/
    #                         resolution (they are different functions
    #                         with different summaries)

    @property
    def name(self) -> str:
        return self.node.name

    @property
    def qualname(self) -> str:
        return f"{self.cls}.{self.name}" if self.cls else self.name

    @property
    def key(self):
        return (self.ctx.rel, self.cls, self.scope, self.name)


def index_functions(ctxs: Sequence[FileContext]) -> List[FnInfo]:
    """Every def in the scan set, including ones nested in other defs
    and inside compound statements (loop bodies, except-import
    fallbacks, match arms)."""
    fns: List[FnInfo] = []
    for ctx in ctxs:
        stack = [(ctx.tree, "", "")]
        while stack:
            node, cls, scope = stack.pop()
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.ClassDef):
                    stack.append((child, child.name, scope))
                elif isinstance(child, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                    fns.append(FnInfo(ctx, child, cls, scope))
                    inner = f"{scope}.{child.name}" if scope \
                        else child.name
                    stack.append((child, cls, inner))
                elif isinstance(child, _CONTAINER_STMT_TYPES):
                    stack.append((child, cls, scope))
    return fns


_CONTAINER_STMT_TYPES = (ast.If, ast.Try, ast.With, ast.AsyncWith,
                         ast.For, ast.AsyncFor, ast.While,
                         ast.ExceptHandler) + tuple(
    getattr(ast, n) for n in ("Match", "match_case") if hasattr(ast, n))

# attribute-call names too generic to resolve by global uniqueness
# (container/protocol vocabulary — resolving `.get()` to some class's
# `get` would build fantasy edges)
GENERIC_ATTRS = frozenset({
    "get", "put", "items", "keys", "values", "append", "add", "update",
    "pop", "close", "open", "read", "write", "run", "start", "stop",
    "join", "split", "copy", "clear", "count", "index", "sort", "submit",
    "encode", "decode",  # str/bytes, not models/encoder.encode
})


class CallGraph:
    """Name-heuristic call graph over the indexed functions. Resolution
    policy (under-reach by design — rules/host_sync.py docstring has
    the rationale): simple names resolve within the module then to a
    globally-unique def; `self.x(...)` resolves within the class; other
    attribute calls resolve only when the method name is defined
    exactly once repo-wide and is not a GENERIC_ATTRS protocol name.

    `ambiguous_names` blocks uniqueness resolution for names known to
    be multiply-defined OUTSIDE this scan set: a `--changed` subset
    scan would otherwise resolve a name the full scan leaves ambiguous
    (the other definition's file not being in the subset), producing
    phantom findings tier-1 never emits."""

    def __init__(self, fns: List[FnInfo],
                 ambiguous_names: frozenset = frozenset()):
        self.fns = fns
        self.ambiguous = ambiguous_names
        self.by_key = {f.key: f for f in fns}
        # GLOBAL resolution tables hold only ADDRESSABLE defs: a def
        # nested inside another function (f.scope) is not importable/
        # callable from outside its frame, so letting it shadow (or be
        # merged with) a same-named module-level def would corrupt
        # both the summaries and the uniqueness resolution. Nested
        # defs resolve LEXICALLY instead (self.scoped): callable from
        # within their enclosing frame's scope chain only — hot
        # functions keep their reach into nested helpers.
        self.by_name: Dict[str, List[FnInfo]] = {}
        self.methods: Dict[tuple, Dict[str, FnInfo]] = {}
        self.module_fns: Dict[str, Dict[str, FnInfo]] = {}
        self.scoped: Dict[tuple, Dict[str, FnInfo]] = {}
        for f in fns:
            if f.scope:
                self.scoped.setdefault(
                    (f.ctx.rel, f.cls, f.scope), {})[f.name] = f
                continue
            self.by_name.setdefault(f.name, []).append(f)
            if f.cls:
                self.methods.setdefault(
                    (f.ctx.rel, f.cls), {})[f.name] = f
            else:
                self.module_fns.setdefault(f.ctx.rel, {})[f.name] = f

    def _unique(self, name: str) -> Optional[FnInfo]:
        if name in self.ambiguous:
            return None
        hits = self.by_name.get(name, ())
        return hits[0] if len(hits) == 1 else None

    def resolve_call(self, fn: FnInfo, call: ast.Call) -> Optional[FnInfo]:
        func = call.func
        if isinstance(func, ast.Name):
            # lexical chain first: defs nested in THIS frame, then in
            # each enclosing frame (Python name resolution order —
            # locals, enclosing, module)
            frame = f"{fn.scope}.{fn.name}" if fn.scope else fn.name
            while frame:
                hit = self.scoped.get(
                    (fn.ctx.rel, fn.cls, frame), {}).get(func.id)
                if hit is not None:
                    return hit
                frame = frame.rpartition(".")[0]
            local = self.module_fns.get(fn.ctx.rel, {}).get(func.id)
            if local is not None:
                return local
            return self._unique(func.id)  # imported def elsewhere
        if isinstance(func, ast.Attribute):
            attr = func.attr
            if is_self_attr(func) is not None and fn.cls:
                mine = self.methods.get((fn.ctx.rel, fn.cls), {}).get(attr)
                if mine is not None:
                    return mine
            if attr in GENERIC_ATTRS:
                return None
            return self._unique(attr)
        return None

    def callees(self, fn: FnInfo) -> Iterable[FnInfo]:
        for node in walk_body(fn.node):
            if isinstance(node, ast.Call):
                target = self.resolve_call(fn, node)
                if target is not None:
                    yield target


class Scan:
    """One lint run's shared repo view. Built once per `run_lint` and
    handed to every `check_scan` rule; the function index, call graph
    and per-function summaries (tools/graftlint/dataflow.py) are all
    computed LAZILY — a rule-scoped run that never touches them pays
    nothing."""

    def __init__(self, ctxs: Sequence[FileContext], root: str,
                 ambiguous_names: frozenset = frozenset()):
        self.ctxs = list(ctxs)
        self.root = root
        self.ambiguous_names = ambiguous_names
        self._functions: Optional[List[FnInfo]] = None
        self._graph: Optional[CallGraph] = None
        self._summaries = None

    @property
    def functions(self) -> List[FnInfo]:
        if self._functions is None:
            self._functions = index_functions(self.ctxs)
        return self._functions

    @property
    def graph(self) -> CallGraph:
        if self._graph is None:
            self._graph = CallGraph(self.functions,
                                    self.ambiguous_names)
        return self._graph

    @property
    def summaries(self):
        """{fn.key: dataflow.Summary} after interprocedural
        propagation."""
        if self._summaries is None:
            from tools.graftlint import dataflow
            self._summaries = dataflow.compute_summaries(self)
        return self._summaries


# ---- shared AST helpers (used by several rules) ----

def call_name(node: ast.Call) -> str:
    """Trailing name of a call: foo(...) -> 'foo', a.b.c(...) -> 'c'."""
    f = node.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return ""


def dotted_name(node: ast.AST) -> str:
    """'a.b.c' for a Name/Attribute chain, '' for anything else."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def is_self_attr(node: ast.AST) -> Optional[str]:
    """'x' when node is `self.x`, else None."""
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"):
        return node.attr
    return None


def walk_body(node: ast.AST, *, into_defs: bool = False):
    """Walk a def/class body WITHOUT descending into nested function /
    class definitions (they are separate symbols with their own
    reachability / lock context)."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        yield n
        if not into_defs and isinstance(
                n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            continue
        stack.extend(ast.iter_child_nodes(n))
