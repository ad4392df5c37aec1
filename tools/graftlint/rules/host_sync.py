"""host-sync-in-hot-path: host transfers inside the latency-critical
call graph.

The hot paths — the jitted train/eval/predict steps, the model's
`predict_device`, and the serving batcher's flush loop — must never
block on a host<->device transfer the author didn't budget for:
`.item()`, `float()/int()` on a device value, `np.asarray` /
`jax.device_get`, `print` of a device value, or a bare
`block_until_ready`. One stray sync serializes the dispatch pipeline
and is invisible to pytest because nothing is wrong, only slow.

Mechanics: build a name-resolved static call graph over the scan set,
BFS from the hot roots, and scan every reachable function body. Roots:

  - any function carrying a jit/pmap/pjit decorator (the steps);
  - `Code2VecModel.predict_device` (the serving device phase);
  - `MicroBatcher._run` and `PredictionServer._run_batch` (the batcher
    flush path — `_batch_fn` is a constructor-injected indirection the
    static graph cannot see through, so both sides are roots).

Sanctioned sync points (not flagged, not traversed): `device_sync` and
`_Span.stop` — the obs helpers whose WHOLE JOB is the explicit,
telemetry-attributed sync (`span(...).stop(sync=tree)`) — and
`fetch_global` (parallel/distributed.py), the ONE named terminal
fetch that ends the predict/eval hot paths (single-process np.asarray
or multi-process allgather; its docstring owns the policy). The
round-11 inline suppressions inside fetch_global are gone with this
round-14 sanction: `code2vec_tpu/parallel/` joined
NO_BASELINE_PREFIXES, and a helper whose whole job is the deliberate
fetch is the same species as device_sync — an explicit, greppable
seam, not an accident this rule could catch. Accidental syncs
(.item(), float(), bare np.asarray) stay flagged everywhere; a NEW
deliberate fetch must either route through fetch_global or earn its
own entry here with a policy docstring.

Call resolution is heuristic by design (plain `ast`, no imports):
simple names resolve within the module then to a globally-unique def;
`self.x(...)` resolves within the class; other attribute calls resolve
only when the method name is defined exactly once repo-wide and is not
a common container-protocol name. Unresolvable calls end traversal —
the rule under-reaches rather than spraying false paths. The function
index and resolver live in core (`Scan.functions` / `Scan.graph`,
ISSUE 14) so the summary layer and the SPMD rules share them; this
rule keeps only its roots, sanctions and violation vocabulary.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Tuple

from tools.graftlint.core import (Finding, FnInfo, Rule, Scan,
                                  call_name, register, walk_body)

RULE = "host-sync-in-hot-path"

_JIT_NAMES = frozenset({"jit", "pmap", "pjit"})

# (class, function) hot roots the call graph cannot discover itself
_ROOT_METHODS = frozenset({
    ("Code2VecModel", "predict_device"),
    ("MicroBatcher", "_run"),
    ("PredictionServer", "_run_batch"),
})

# the explicit sync/fetch seams (module docstring has the policy):
# obs helpers + the parallel layer's one terminal result fetch
_SANCTIONED = frozenset({("", "device_sync"), ("_Span", "stop"),
                         ("", "fetch_global")})

# numpy module aliases whose `.asarray` is a device->host fetch when fed
# a jax array (jnp.asarray is host->device and is NOT flagged)
_NP_ALIASES = frozenset({"np", "numpy", "onp"})


def _has_jit_decorator(node: ast.AST) -> bool:
    for dec in getattr(node, "decorator_list", ()):
        for n in ast.walk(dec):
            if isinstance(n, ast.Name) and n.id in _JIT_NAMES:
                return True
            if isinstance(n, ast.Attribute) and n.attr in _JIT_NAMES:
                return True
    return False


def _mentions_shape_math(node: ast.AST) -> bool:
    """True when an expression is shape/dtype bookkeeping, not a device
    value: touching .shape/.ndim/.size/.dtype/len() or made purely of
    constants. float(loss) flags; int(x.shape[0]) does not."""
    all_const = True
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr in (
                "shape", "ndim", "size", "dtype"):
            return True
        if isinstance(n, ast.Call) and call_name(n) == "len":
            return True
        if not isinstance(n, (ast.Constant, ast.BinOp, ast.UnaryOp,
                              ast.operator, ast.unaryop, ast.expr_context,
                              ast.Tuple, ast.List)):
            all_const = False
    return all_const


def _is_sanctioned(fn: FnInfo) -> bool:
    return ((fn.cls, fn.name) in _SANCTIONED
            or ("", fn.name) in _SANCTIONED)


def _scan_violations(fn: FnInfo, root_label: str) -> Iterable[Finding]:
    # which root reached us is BFS-order-dependent context -> `detail`
    # (outside the baseline identity), never part of the message
    via = f"hot path via {root_label}" if root_label != fn.qualname \
        else ""
    for node in walk_body(fn.node):
        if not isinstance(node, ast.Call):
            continue
        name = call_name(node)
        msg = None
        if name == "item" and isinstance(node.func, ast.Attribute) \
                and not node.args and not node.keywords:
            msg = ".item() forces a device->host sync"
        elif name in ("float", "int") and isinstance(node.func, ast.Name) \
                and len(node.args) == 1 \
                and not _mentions_shape_math(node.args[0]):
            msg = (f"{name}() on a runtime value blocks on the device "
                   "if it is a jax array")
        elif name == "asarray" and isinstance(node.func, ast.Attribute) \
                and isinstance(node.func.value, ast.Name) \
                and node.func.value.id in _NP_ALIASES:
            msg = "np.asarray fetches device arrays to the host"
        elif name == "device_get":
            msg = "jax.device_get is an explicit device->host fetch"
        elif name == "print" and isinstance(node.func, ast.Name):
            msg = ("print in a hot function stalls the dispatch queue "
                   "(and syncs if handed a device value)")
        elif name == "block_until_ready":
            msg = "bare block_until_ready in a hot function"
        if msg:
            yield Finding(
                rule=RULE, path=fn.ctx.rel, line=node.lineno,
                symbol=fn.qualname, detail=via,
                message=(f"{msg}; use the obs "
                         "span(...).stop(sync=...) helpers for a "
                         "deliberate sync, or move this off the hot "
                         "path"))


@register
class HostSyncRule(Rule):
    name = RULE
    description = ("host transfers (.item(), float()/int(), np.asarray, "
                   "print, bare block_until_ready) in functions "
                   "reachable from the jitted step / predict / "
                   "batcher-flush paths")

    def check_scan(self, scan: Scan) -> Iterable[Finding]:
        fns = scan.functions
        graph = scan.graph
        roots = [f for f in fns
                 if (_has_jit_decorator(f.node)
                     or (f.cls, f.name) in _ROOT_METHODS)
                 and not _is_sanctioned(f)]
        # BFS; remember which root first reached each function so the
        # message can say WHY it is considered hot (keys are
        # FnInfo.key 4-tuples: rel, cls, scope, name)
        reached: Dict[tuple, str] = {}
        queue: List[Tuple[FnInfo, str]] = [(f, f.qualname) for f in roots]
        for f, label in queue:
            reached.setdefault(f.key, label)
        i = 0
        while i < len(queue):
            fn, label = queue[i]
            i += 1
            for callee in graph.callees(fn):
                if _is_sanctioned(callee) or callee.key in reached:
                    continue
                reached[callee.key] = label
                queue.append((callee, label))
        findings: List[Finding] = []
        for fn in fns:
            label = reached.get(fn.key)
            if label is None or _is_sanctioned(fn):
                continue
            findings.extend(_scan_violations(fn, label))
        return findings
