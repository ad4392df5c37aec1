"""graftlint (ISSUE 4; interprocedural since ISSUE 14): the suite is
tier-1 — the repo must lint clean against its checked-in baseline,
every rule must catch its fixture true-positives and ignore its tricky
false-positives, and the whole two-pass scan (per-file rules + the
call-summary fixpoint) must run fast (< 60 s) WITHOUT importing JAX or
TensorFlow
(blocked-module subprocess proof, the test_obs_guard.py pattern — a
linter that drags in a backend couldn't gate commits on a CPU image).
"""

import json
import os
import subprocess
import sys
import time

import pytest

from tools.graftlint import baseline as baseline_mod
from tools.graftlint.core import (DEFAULT_PATHS, REPO_ROOT, Finding,
                                  FileContext, all_rules, run_lint)
from tools.graftlint.rules.config_drift import check_config_drift
from tools.graftlint.rules.test_markers import (TestMarkerRule,
                                                registered_markers)

REPO = REPO_ROOT
FIXTURES = os.path.join(REPO, "tests", "graftlint_fixtures")

# every registered rule — extended by the ISSUE 12 dataflow trio and
# the ISSUE 14 interprocedural pair; the no-baseline gate below runs
# ALL of them, so serving/obs/training/ops/parallel/resilience must
# come up clean under the new rules too
ALL_RULES = {"host-sync-in-hot-path", "retrace-hazard",
             "lock-discipline", "config-drift", "test-marker-hygiene",
             "swallowed-error", "donation-safety", "thread-handoff",
             "resource-leak", "spmd-divergence", "nondeterminism"}


def _fx(name):
    return os.path.join(FIXTURES, name)


# ---- the repo itself must lint clean (the CI gate) ----

@pytest.fixture(scope="module")
def repo_scan():
    """ONE timed repo-wide scan shared by the gate tests (it dominates
    the suite's runtime; the assertions are independent views of it).
    -> (findings, elapsed_seconds)"""
    t0 = time.perf_counter()
    findings = run_lint(DEFAULT_PATHS, root=REPO)
    return findings, time.perf_counter() - t0


@pytest.fixture(scope="module")
def repo_findings(repo_scan):
    return repo_scan[0]


def test_all_eleven_rules_registered():
    assert set(all_rules()) == ALL_RULES
    assert len(ALL_RULES) == 11


def test_full_scan_performance(repo_scan):
    """Tier-1 guard (ISSUE 12 satellite, re-baselined for the ISSUE 14
    TWO-PASS scan): the full-repo scan with all 11 rules — including
    the summary pass + call-graph fixpoint — must stay comfortably
    inside the pre-commit budget; this bound is how we notice if a
    rule change (or the fixpoint) quietly goes quadratic. Generous:
    the two-pass scan measures ~8-10 s on a loaded CI core."""
    _findings, elapsed = repo_scan
    assert elapsed < 60.0, f"full graftlint scan took {elapsed:.1f}s"


def test_repo_lints_clean_against_baseline(repo_findings):
    entries = baseline_mod.load()
    new, old, stale = baseline_mod.split(repo_findings, entries)
    assert new == [], "\n".join(f.render() for f in new)
    assert stale == [], f"stale baseline entries (regenerate): {stale}"


def test_serving_and_obs_trees_are_finding_free(repo_findings):
    """ISSUE 4 acceptance (extended to training/ with the async
    checkpoint writer, ops/ with the fused sparse-update kernel):
    EMPTY baseline for the no-baseline trees — and not just
    baselined-away: zero findings at all."""
    dirty = [f for f in repo_findings
             if f.path.startswith(baseline_mod.NO_BASELINE_PREFIXES)]
    assert dirty == [], "\n".join(f.render() for f in dirty)
    assert not [e for e in baseline_mod.load()
                if e["path"].startswith(
                    baseline_mod.NO_BASELINE_PREFIXES)]


def test_slow_marker_registered():
    """Tier-1 deselects with -m 'not slow' (the guard the marker rule
    generalizes — keep the direct assertion too)."""
    assert "slow" in registered_markers(os.path.join(REPO, "pytest.ini"))


# ---- per-rule fixtures: true positives hit, tricky FPs don't ----

def _rule_findings(rule, paths):
    return run_lint(paths, root=REPO, rules=[rule])


def test_host_sync_fixtures():
    tp = _rule_findings("host-sync-in-hot-path", [_fx("host_sync_tp.py")])
    hits = {(f.symbol, f.line) for f in tp}
    assert len(tp) == 7, "\n".join(f.render() for f in tp)
    assert {s for s, _ in hits} == {"hot_step", "fetch_helper",
                                    "MicroBatcher._run",
                                    "loop_defined_step"}
    msgs = " ".join(f.message for f in tp)
    for needle in (".item()", "float()", "print", "block_until_ready",
                   "np.asarray", "device_get"):
        assert needle in msgs, needle
    # two-hop reachability: the asarray sits two calls below the root;
    # the root label lives in `detail`, OUTSIDE the baseline identity
    # (BFS order must not be able to invalidate baseline entries)
    two_hop = [f for f in tp if f.symbol == "fetch_helper"]
    assert two_hop and all("via hot_step" in f.detail
                           and "via" not in f.message for f in two_hop)
    fp = _rule_findings("host-sync-in-hot-path", [_fx("host_sync_fp.py")])
    assert fp == [], "\n".join(f.render() for f in fp)


def test_retrace_fixtures():
    tp = _rule_findings("retrace-hazard", [_fx("retrace_tp.py")])
    msgs = [f.message for f in tp]
    for needle in ("inside a loop", "compiles on EVERY call",
                   "static_argnums must be a literal",
                   "static_argnames must be a literal",
                   "Python scalar literal", "dict literal",
                   "shape-derived branch"):
        assert any(needle in m for m in msgs), needle
    fp = _rule_findings("retrace-hazard", [_fx("retrace_fp.py")])
    assert fp == [], "\n".join(f.render() for f in fp)


def test_lock_discipline_fixtures():
    tp = _rule_findings("lock-discipline", [_fx("lock_tp.py")])
    assert {f.symbol for f in tp} == {
        "RacyQueue._running", "RacyQueue._items", "RacyCond._depth",
        "RacyClassLock._size", "RacyUnpack._thread",
        "RacyUnpack._assembled"}
    assert all("(locked)" in f.message for f in tp)  # names both sites
    fp = _rule_findings("lock-discipline", [_fx("lock_fp.py")])
    assert fp == [], "\n".join(f.render() for f in fp)


def test_config_drift_fixtures():
    tp_dir = os.path.join(FIXTURES, "config_drift_tp")
    tp = check_config_drift(os.path.join(tp_dir, "config.py"),
                            os.path.join(tp_dir, "README.md"))
    symbols = {f.symbol for f in tp}
    assert symbols == {"--dead_flag", "ns.phantom", "self.BTACH_SIZE",
                       "--undocumented", "--stale_flag", "ORPHAN_ATTR",
                       "WIRED_BUT_LISTED", "GHOST_CONSTANT"}, symbols
    fp_dir = os.path.join(FIXTURES, "config_drift_fp")
    fp = check_config_drift(os.path.join(fp_dir, "config.py"),
                            os.path.join(fp_dir, "README.md"))
    assert fp == [], "\n".join(f.render() for f in fp)


def test_swallowed_error_fixtures():
    tp = _rule_findings("swallowed-error", [_fx("swallowed_tp.py")])
    assert {f.symbol for f in tp} == {
        "classic_pass", "bound_but_unused", "bare_except_continue",
        "base_exception_pass", "broad_inside_tuple",
        "docstring_only_body", "closest"}
    assert all("swallows the error" in f.message for f in tp)
    fp = _rule_findings("swallowed-error", [_fx("swallowed_fp.py")])
    assert fp == [], "\n".join(f.render() for f in fp)


def test_donation_safety_fixtures():
    """ISSUE 12 acceptance: a post-donation read of a make_train_step-
    style step's params must flag; the snapshot_state pattern (and the
    rebind idiom) must stay quiet."""
    tp = _rule_findings("donation-safety", [_fx("donation_tp.py")])
    assert {f.symbol for f in tp} == {
        "read_after_factory_step_donation", "return_of_donated",
        "aliased_container_read", "donate_argnames_read",
        "closure_capture_after_donation", "ModelWithStep.train_one"}
    msgs = " ".join(f.message for f in tp)
    assert "donated" in msgs and "snapshot_state" in msgs
    # the alias shape names the flow; the closure shape names capture
    assert any("through an alias" in f.message for f in tp)
    assert any("captured by a nested function" in f.message for f in tp)
    # the donation site is context, NOT baseline identity (line moves
    # must not resurrect entries)
    assert all("donated at line" in f.detail
               and "line" not in f.message for f in tp)
    fp = _rule_findings("donation-safety", [_fx("donation_fp.py")])
    assert fp == [], "\n".join(f.render() for f in fp)


def test_thread_handoff_fixtures():
    tp = _rule_findings("thread-handoff", [_fx("handoff_tp.py")])
    assert {f.symbol for f in tp} == {
        "RacyBatcher.submit", "RacyBatcher.submit_batch",
        "thread_args_mutation", "executor_submit_mutation",
        "aug_extend_after_put", "SharedStore.publish",
        "raising_monitor"}
    # every escape vector is represented
    msgs = " ".join(f.message for f in tp)
    for needle in ("Thread(...)", ".put(...)", ".submit(...)",
                   "self._current = ..."):
        assert needle in msgs, needle
    # the monitor sub-check: never raise from the monitor thread
    monitor = [f for f in tp if f.symbol == "raising_monitor"]
    assert monitor and "monitor" in monitor[0].message \
        and "record the failure" in monitor[0].message
    fp = _rule_findings("thread-handoff", [_fx("handoff_fp.py")])
    assert fp == [], "\n".join(f.render() for f in fp)


def test_resource_leak_fixtures():
    """ISSUE 12 acceptance: the PR-6 leaked-span shape must flag;
    try/finally, except-handler and context-manager releases must stay
    quiet."""
    tp = _rule_findings("resource-leak", [_fx("leak_tp.py")])
    syms = {f.symbol for f in tp}
    assert syms == {
        "leaked_span_on_error", "telemetry_span_error_window",
        "early_return_leaks", "thread_never_joined",
        "submit_without_barrier", "acquire_without_release"}
    msgs = " ".join(f.message for f in tp)
    assert "PR-6 leaked-span class" in msgs       # the error-path form
    assert "not released on every path" in msgs   # the exit-leak form
    # early_return_leaks exhibits BOTH hazards on one span
    assert len([f for f in tp
                if f.symbol == "early_return_leaks"]) == 2
    fp = _rule_findings("resource-leak", [_fx("leak_fp.py")])
    assert fp == [], "\n".join(f.render() for f in fp)


def test_spmd_divergence_fixtures():
    """ISSUE 14 acceptance: every collective-under-divergent-control
    shape flags (direct, assigned-rank, early exit, exception handler,
    IfExp arm, writer submit, per-host loop, and the summary-hop
    reaches); the uniform/audited shapes stay quiet."""
    tp = _rule_findings("spmd-divergence", [_fx("spmd_tp.py")])
    assert {f.symbol for f in tp} == {
        "branch_on_process_index", "branch_on_assigned_rank",
        "divergent_early_exit", "collective_in_exception_handler",
        "interprocedural_reach", "divergent_test_via_summary",
        "ternary_collective", "RankedSaver.maybe_submit",
        "loop_over_local_devices"}
    msgs = " ".join(f.message for f in tp)
    assert "cohort deadlocks" in msgs
    for needle in ("collective `psum`", "shard_map",
                   "exception handler", "early exit",
                   "async checkpoint writer"):
        assert needle in msgs, needle
    # the divergent-site line/via chain is context, NOT baseline
    # identity (line moves must not resurrect entries)
    assert all("divergent control:" in f.detail for f in tp)
    # the one-hop reach names the callee the effect came through
    via = [f for f in tp if f.symbol == "interprocedural_reach"]
    assert via and "inherited via _sync_helper" in via[0].detail
    fp = _rule_findings("spmd-divergence", [_fx("spmd_fp.py")])
    assert fp == [], "\n".join(f.render() for f in fp)


def test_nondeterminism_fixtures():
    """ISSUE 14 acceptance: wall-clock/global-rng/fs-order/set-order/
    id() into rng seams, tensors, seed kwargs and checkpointed state
    all flag; the sanctioned seams (step-keyed fold_in, seeded
    instance streams, sorted listings, set membership, telemetry
    timestamps, per-host row tags, dither_from_index) stay quiet."""
    tp = _rule_findings("nondeterminism", [_fx("nondet_tp.py")])
    assert {f.symbol for f in tp} == {
        "clock_seeded_key", "clock_fold_in", "global_rng_tensor",
        "set_order_tensor", "listing_order_rows",
        "glob_into_checkpoint", "loop_var_into_checkpoint",
        "seed_kwarg_from_clock", "interprocedural_source",
        "object_identity_seed"}
    msgs = " ".join(f.message for f in tp)
    for needle in ("wall clock", "global random stream",
                   "set iteration order", "filesystem listing order",
                   "rng seam", "tensor construction",
                   "checkpointed state", "resume-parity"):
        assert needle in msgs, needle
    # the source site rides in `detail` (outside baseline identity);
    # the one-hop source names the returning callee
    assert all("source:" in f.detail for f in tp)
    hop = [f for f in tp if f.symbol == "interprocedural_source"]
    assert hop and "returned by `_wall_clock_stamp`" in hop[0].detail
    fp = _rule_findings("nondeterminism", [_fx("nondet_fp.py")])
    assert fp == [], "\n".join(f.render() for f in fp)


# ---- the summary layer itself (ISSUE 14 satellite) ----

def test_nested_helper_keeps_hot_path_reach(tmp_path):
    """Review round: excluding nested defs from GLOBAL resolution must
    not cost the lexical reach — a host sync in a helper nested inside
    a jitted step still flags (nested defs resolve through the
    enclosing frame's scope chain), while a nested def can no longer
    shadow a same-named module-level def repo-wide."""
    p = tmp_path / "hot.py"
    p.write_text(
        "import jax\n"
        "import numpy as np\n\n\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    def fetch(v):\n"
        "        return float(np.asarray(v))\n"
        "    return fetch(x)\n")
    fs = run_lint([str(p)], root=str(tmp_path),
                  rules=["host-sync-in-hot-path"])
    assert {f.symbol for f in fs} == {"fetch"}, \
        "\n".join(f.render() for f in fs)


def test_summaries_two_hop_reach():
    """A hazard TWO resolved calls below the divergent/sinking site
    fires only through the propagated summaries — nothing
    intraprocedural can see it."""
    spmd = _rule_findings("spmd-divergence",
                          [_fx("summaries_twohop_tp.py")])
    assert {f.symbol for f in spmd} == {"divergent_two_hops_up"}
    assert "inherited via _middle" in spmd[0].detail
    nondet = _rule_findings("nondeterminism",
                            [_fx("summaries_twohop_tp.py")])
    assert {f.symbol for f in nondet} == {"seeded_two_hops_up"}
    assert "returned by `_stamp`" in nondet[0].detail


def test_summaries_terminate_on_cycles():
    """Recursion and mutual call cycles must converge (facts are
    monotone finite sets): summaries come back, clean cycles stay
    empty, and an effect inside a cycle propagates to every member —
    while the uniform caller produces no finding."""
    from tools.graftlint.core import Scan

    ctx = FileContext(_fx("summaries_cycle_fp.py"), REPO)
    scan = Scan([ctx], REPO)
    sums = {s.qualname: s for s in scan.summaries.values()}
    assert sums["clean_self_recursive"].collective == {}
    assert sums["ping"].collective == {} and sums["pong"].nondet == {}
    for member in ("cyc_a", "cyc_b", "uniform_cycle_user"):
        assert any("psum" in lbl for lbl in sums[member].collective), \
            member
    for rule in ("spmd-divergence", "nondeterminism"):
        fs = _rule_findings(rule, [_fx("summaries_cycle_fp.py")])
        assert fs == [], "\n".join(f.render() for f in fs)


def test_summaries_record_escaping_and_donated_params():
    """The ISSUE 14 summary spec: params that escape (thread/queue/
    attribute/closure) and params the body donates are recorded, and
    donation propagates so donation-safety sees through wrappers."""
    import textwrap

    from tools.graftlint.core import Scan

    src = textwrap.dedent("""\
        import jax, threading, queue

        step = jax.jit(lambda p, o: (p, o), donate_argnums=(0, 1))

        def wrapper(params, opt, batch):
            return step(params, opt)

        def two_hop_wrapper(params, opt, batch):
            return wrapper(params, opt, batch)

        def escapes(params, q, store):
            q.put(params)
            store.latest = params
            def closure():
                return params
            return closure

        def caller(params, opt, batch, save):
            new_p, new_o = two_hop_wrapper(params, opt, batch)
            save(params)  # read-after-donation, two wrappers deep
            return new_p, new_o
    """)
    path = os.path.join(REPO, "tests", "graftlint_fixtures")
    tmp = os.path.join(path, "_summary_params_tmp.py")
    with open(tmp, "w") as f:
        f.write(src)
    try:
        ctx = FileContext(tmp, REPO)
        scan = Scan([ctx], REPO)
        sums = {s.qualname: s for s in scan.summaries.values()}
        assert sums["wrapper"].donated_params == {0: "params", 1: "opt"}
        assert sums["two_hop_wrapper"].donated_params == {
            0: "params", 1: "opt"}
        assert sums["escapes"].escaping_params == {"params"}
        dn = run_lint([tmp], root=REPO, rules=["donation-safety"])
        assert [f.symbol for f in dn] == ["caller"], \
            "\n".join(f.render() for f in dn)
        assert "`params` is read after being donated" in dn[0].message
    finally:
        os.remove(tmp)


def test_dataflow_sees_defs_in_match_and_async_with():
    """Regression (review): a def nested in a match-case arm or an
    async-with body is still a frame — a span leak there must flag."""
    import ast as ast_mod
    from tools.graftlint import dataflow as df
    src = (
        "async def outer(cm, mode, tracer, req):\n"
        "    match mode:\n"
        "        case 'a':\n"
        "            def in_match():\n"
        "                sp = tracer.start_span('x')\n"
        "                handle(req)\n"
        "                sp.end()\n"
        "    async with cm:\n"
        "        def in_async_with():\n"
        "            sp2 = tracer.start_span('y')\n"
        "            handle(req)\n"
        "            sp2.end()\n")
    names = {fn.name for fn, _cls in
             df.iter_functions(ast_mod.parse(src))}
    assert {"in_match", "in_async_with"} <= names


def test_marker_fixtures():
    rule = all_rules()["test-marker-hygiene"]
    tp = list(rule.check_ctx(FileContext(_fx("markers_tp.py"), REPO),
                             {"slow"}))
    assert {f.symbol for f in tp} == {
        "pytest.mark.slwo", "pytest.mark.sloow", "test_long_soak",
        "test_duration_cli"}
    fp = list(rule.check_ctx(FileContext(_fx("markers_fp.py"), REPO),
                             {"slow"}))
    assert fp == [], "\n".join(f.render() for f in fp)


# ---- suppressions and the baseline workflow ----

def test_inline_and_file_suppressions(tmp_path):
    bad = ("import jax\n\n\n"
           "@jax.jit\n"
           "def hot(x):\n"
           "    return x.item()\n")
    p = tmp_path / "mod.py"
    p.write_text(bad)
    assert len(run_lint([str(p)], root=str(tmp_path),
                        rules=["host-sync-in-hot-path"])) == 1
    p.write_text(bad.replace(
        "return x.item()",
        "return x.item()  # graftlint: disable=host-sync-in-hot-path"))
    assert run_lint([str(p)], root=str(tmp_path),
                    rules=["host-sync-in-hot-path"]) == []
    p.write_text("# graftlint: disable-file=all\n" + bad)
    assert run_lint([str(p)], root=str(tmp_path)) == []


def test_baseline_split_and_write(tmp_path):
    f1 = Finding("r", "a.py", 3, "m1", "s")
    f2 = Finding("r", "a.py", 9, "m2", "s")
    path = str(tmp_path / "base.json")
    baseline_mod.write([f1], path)
    new, old, stale = baseline_mod.split([f1, f2],
                                         baseline_mod.load(path))
    assert (new, old, stale) == ([f2], [f1], [])
    # line moves don't resurrect a grandfathered finding
    moved = Finding("r", "a.py", 300, "m1", "s")
    new, old, _ = baseline_mod.split([moved], baseline_mod.load(path))
    assert new == [] and old == [moved]
    # a fixed finding reports its entry as stale
    _, _, stale = baseline_mod.split([], baseline_mod.load(path))
    assert len(stale) == 1
    # a SECOND instance of a baselined finding is NEW (duplicate-aware)
    new, old, _ = baseline_mod.split([f1, moved],
                                     baseline_mod.load(path))
    assert len(new) == 1 and len(old) == 1


def test_baseline_refuses_serving_and_obs(tmp_path):
    path = str(tmp_path / "base.json")
    bad = Finding("lock-discipline", "code2vec_tpu/serving/batcher.py",
                  1, "m", "s")
    bad_training = Finding("lock-discipline",
                           "code2vec_tpu/training/checkpoint.py",
                           1, "m", "s")
    bad_ops = Finding("host-sync-in-hot-path",
                      "code2vec_tpu/ops/pallas_sparse_update.py",
                      1, "m", "s")
    bad_parallel = Finding("host-sync-in-hot-path",
                           "code2vec_tpu/parallel/distributed.py",
                           1, "m", "s")
    bad_resilience = Finding("swallowed-error",
                             "code2vec_tpu/resilience/retry.py",
                             1, "m", "s")
    # ISSUE 14 satellite: the new interprocedural rules are refused
    # entries under training/, parallel/ and resilience/ from day one —
    # a divergent collective or a nondeterministic parity leak in
    # those trees is a bug to fix, never debt to grandfather
    bad_spmd = Finding("spmd-divergence",
                       "code2vec_tpu/training/checkpoint.py", 1, "m", "s")
    bad_spmd_par = Finding("spmd-divergence",
                           "code2vec_tpu/parallel/distributed.py",
                           1, "m", "s")
    bad_nondet = Finding("nondeterminism",
                         "code2vec_tpu/resilience/faults.py", 1, "m", "s")
    bad_nondet_tr = Finding("nondeterminism",
                            "code2vec_tpu/training/sparse_update.py",
                            1, "m", "s")
    # ISSUE 17 satellite: the fleet plane joins the obs/ fence from
    # day one — a leak or swallowed error in the cohort collector
    # (the thing that watches everyone else) is a bug to fix, never
    # debt to grandfather
    bad_fleet = Finding("resource-leak",
                        "code2vec_tpu/obs/fleet.py", 1, "m", "s")
    # ISSUE 18 satellite: the external serving plane lands inside the
    # fenced serving/ tree — the front-end, replica pool, reload
    # watcher and autoscaler answer live traffic, so a lock slip or a
    # leaked thread there is a bug to fix, never debt to grandfather
    bad_frontend = Finding("thread-handoff",
                           "code2vec_tpu/serving/frontend.py",
                           1, "m", "s")
    bad_replicas = Finding("lock-discipline",
                           "code2vec_tpu/serving/replicas.py",
                           1, "m", "s")
    bad_reload = Finding("resource-leak",
                         "code2vec_tpu/serving/reload.py", 1, "m", "s")
    bad_scaler = Finding("nondeterminism",
                         "code2vec_tpu/serving/autoscale.py",
                         1, "m", "s")
    ok = Finding("retrace-hazard", "tools/x.py", 1, "m", "s")
    refused = baseline_mod.write(
        [bad, bad_training, bad_ops, bad_parallel, bad_resilience,
         bad_spmd, bad_spmd_par, bad_nondet, bad_nondet_tr,
         bad_fleet, bad_frontend,
         bad_replicas, bad_reload, bad_scaler, ok],
        path)
    assert refused == [bad, bad_training, bad_ops, bad_parallel,
                       bad_resilience, bad_spmd, bad_spmd_par,
                       bad_nondet, bad_nondet_tr, bad_fleet, bad_frontend,
                       bad_replicas, bad_reload, bad_scaler]
    assert [e["path"] for e in baseline_mod.load(path)] == ["tools/x.py"]


def test_no_baseline_prefixes_cover_parallel():
    """ISSUE 9: the distribution layer is fenced — fetch_global is a
    sanctioned seam (rules/host_sync._SANCTIONED), not a suppression
    or a baseline entry."""
    assert "code2vec_tpu/parallel/" in baseline_mod.NO_BASELINE_PREFIXES
    from tools.graftlint.rules.host_sync import _SANCTIONED
    assert ("", "fetch_global") in _SANCTIONED


# ---- CLI: platform-free, fast, machine-readable ----

def test_cli_runs_clean_without_jax_or_tf(tmp_path):
    """The pre-commit gate (`python -m tools.graftlint`) must exit 0 on
    the current tree with BOTH jax and tensorflow import-blocked: the
    AST walk may not touch either (tier-1 runs on bare CPU images, and
    the scan-perf budget leaves no room for a backend init). The
    timeout tracks the two-pass (ISSUE 14) scan-perf guard's bound."""
    blocker = tmp_path / "block"
    blocker.mkdir()
    for mod in ("jax", "tensorflow"):
        (blocker / f"{mod}.py").write_text(
            f"raise ImportError('{mod} blocked by test_graftlint')\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(blocker), REPO] + ([env["PYTHONPATH"]]
                                if env.get("PYTHONPATH") else []))
    r = subprocess.run([sys.executable, "-m", "tools.graftlint"],
                       cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 findings" in r.stdout
    # ALL eleven rules ran under the import block — the dataflow core
    # (ISSUE 12) and the two-pass summary layer (ISSUE 14) must hold
    # parse-never-import like everything else
    assert f"rules: {len(ALL_RULES)})" in r.stdout


def test_cli_sarif_format(tmp_path, capsys):
    """ISSUE 14 satellite: `--format sarif` emits valid SARIF 2.1.0 —
    all 11 rules in the driver table, one result per NEW finding with
    rule id + uri + startLine — while text/json stay untouched. Exit
    semantics match json (1 on findings)."""
    from tools.graftlint.__main__ import main

    rc = main(["--format", "sarif", "--rules", "config-drift",
               "code2vec_tpu"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "graftlint"
    assert {r["id"] for r in run["tool"]["driver"]["rules"]} == ALL_RULES
    assert run["results"] == []
    # a planted finding renders as a SARIF result
    p = tmp_path / "bad.py"
    p.write_text("def f():\n"
                 "    try:\n"
                 "        g()\n"
                 "    except Exception:\n"
                 "        pass\n")
    rc = main(["--format", "sarif", "--root", str(tmp_path),
               "--baseline", str(tmp_path / "none.json"), str(p)])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    res = doc["runs"][0]["results"]
    assert len(res) == 1 and res[0]["ruleId"] == "swallowed-error"
    loc = res[0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "bad.py"
    assert loc["region"]["startLine"] == 4


def test_cli_json_format_and_rule_selection(capsys):
    from tools.graftlint.__main__ import main
    rc = main(["--format", "json", "--rules", "config-drift",
               "code2vec_tpu"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["findings"] == []
    assert main(["--rules", "no-such-rule"]) == 2
    capsys.readouterr()


def test_cli_guards_partial_baseline_and_bad_paths(tmp_path, capsys):
    from tools.graftlint.__main__ import main
    # a partial-scope --write-baseline would silently drop every
    # out-of-scope grandfathered entry — refused outright
    assert main(["--write-baseline", "--rules", "config-drift"]) == 2
    assert main(["--write-baseline", "tools"]) == 2
    # a typo'd path scanning zero files must not report "clean"
    assert main(["serving"]) == 2
    capsys.readouterr()


def test_changed_py_files_tracks_git(tmp_path):
    """--changed's file list (ISSUE 12 satellite): worktree diff +
    untracked, scan-set-scoped, fixture dirs excluded, deletions
    dropped."""
    from tools.graftlint.__main__ import changed_py_files
    repo = str(tmp_path / "r")
    os.makedirs(os.path.join(repo, "tools", "graftlint_fixtures"))
    os.makedirs(os.path.join(repo, "docs"))

    def git(*args):
        subprocess.run(["git", "-c", "user.email=t@t",
                        "-c", "user.name=t", *args], cwd=repo,
                       check=True, capture_output=True)

    def write(rel, text="x = 1\n"):
        with open(os.path.join(repo, rel), "w") as f:
            f.write(text)

    git("init", "-q")
    write("tools/clean.py")
    write("tools/gone.py")
    git("add", "-A")
    git("commit", "-qm", "seed")
    assert changed_py_files(repo) == []
    write("tools/clean.py", "x = 2\n")          # modified
    write("tools/fresh.py")                      # untracked
    write("tools/graftlint_fixtures/tp.py")      # excluded dir
    write("docs/outside.py")                     # outside the scan set
    write("tools/notes.txt")                     # not .py
    os.remove(os.path.join(repo, "tools", "gone.py"))  # deleted
    assert changed_py_files(repo) == ["tools/clean.py",
                                      "tools/fresh.py"]


def test_cli_changed_mode_gates_a_diff(tmp_path, capsys):
    """`--changed` end-to-end on a HERMETIC tmp git repo (linting the
    developer's live worktree here would fail on THEIR in-flight
    changes): a clean modified file passes, a planted finding fails,
    and the flag refuses path arguments / --write-baseline
    combinations that would silently narrow the gate."""
    from tools.graftlint.__main__ import main
    repo = str(tmp_path / "r")
    os.makedirs(os.path.join(repo, "tools"))

    def git(*args):
        subprocess.run(["git", "-c", "user.email=t@t",
                        "-c", "user.name=t", *args], cwd=repo,
                       check=True, capture_output=True)

    def write(rel, text):
        with open(os.path.join(repo, rel), "w") as f:
            f.write(text)

    git("init", "-q")
    write("tools/mod.py", "x = 1\n")
    git("add", "-A")
    git("commit", "-qm", "seed")
    write("tools/mod.py", "y = 2\n")
    assert main(["--changed", "--root", repo]) == 0
    write("tools/mod.py",
          "def f():\n"
          "    try:\n"
          "        g()\n"
          "    except Exception:\n"
          "        pass\n")
    assert main(["--changed", "--root", repo]) == 1
    out = capsys.readouterr().out
    assert "swallowed-error" in out
    assert main(["--changed", "tools"]) == 2
    assert main(["--changed", "--write-baseline"]) == 2
    capsys.readouterr()


def test_cli_changed_mode_is_summary_aware(tmp_path, capsys):
    """ISSUE 14 satellite, both directions of the one-hop blast
    radius: (a) a changed CALLEE body can change a CALLER's findings
    one hop up, so the gate re-lints the callers' files; (b) a changed
    CALL SITE can only be judged with its callee's summary present, so
    the gate pulls the callees' files into the scan set too — editing
    ONLY the caller of an unchanged collective helper must still flag
    the new divergent call (review round: the gate used to pass what
    the full scan then failed on)."""
    from tools.graftlint.__main__ import main, summary_scope
    repo = str(tmp_path / "r")
    os.makedirs(os.path.join(repo, "tools"))

    def git(*args):
        subprocess.run(["git", "-c", "user.email=t@t",
                        "-c", "user.name=t", *args], cwd=repo,
                       check=True, capture_output=True)

    def write(rel, text):
        with open(os.path.join(repo, rel), "w") as f:
            f.write(text)

    git("init", "-q")
    write("tools/callee.py", "def helper(x):\n    return x\n")
    write("tools/caller.py",
          "from tools.callee import helper\n\n\n"
          "def top(x):\n"
          "    try:\n"
          "        return helper(x)\n"
          "    except Exception:\n"
          "        pass\n")
    write("tools/unrelated.py", "def lonely():\n    return 1\n")
    git("add", "-A")
    git("commit", "-qm", "seed")
    # (a) only the callee changes; the planted finding is in caller.py
    write("tools/callee.py", "def helper(x):\n    return x + 1\n")
    assert summary_scope(repo, ["tools/callee.py"])[0] == [
        "tools/caller.py"]
    rc = main(["--changed", "--root", repo])
    out = capsys.readouterr().out
    assert rc == 1, out
    assert "caller/callee file" in out
    assert "tools/caller.py" in out and "swallowed-error" in out
    assert "unrelated" not in out  # one hop, not the whole repo
    git("add", "-A")
    git("commit", "-qm", "callee change")
    # (b) only the CALLER changes: a new process_index() branch around
    # the unchanged collective helper — resolvable only because the
    # gate pulls sync.py into the scan set
    write("tools/sync.py",
          "import jax\n\n\n"
          "def sync_helper(x):\n"
          "    return jax.lax.psum(x, 'data')\n")
    git("add", "-A")
    git("commit", "-qm", "fix finding; add helper")
    write("tools/caller.py",
          "import jax\n\nfrom tools.sync import sync_helper\n\n\n"
          "def top(x):\n"
          "    if jax.process_index() == 0:\n"
          "        return sync_helper(x)\n"
          "    return x\n")
    assert summary_scope(repo, ["tools/caller.py"])[0] == [
        "tools/sync.py"]
    rc = main(["--changed", "--root", repo])
    out = capsys.readouterr().out
    assert rc == 1, out
    assert "spmd-divergence" in out and "tools/caller.py" in out
    # per-file-rules-only runs skip the expansion (the fast path the
    # gate exists to preserve) — and therefore don't flag
    rc = main(["--changed", "--root", repo,
               "--rules", "swallowed-error"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "caller/callee" not in out
    # (b') TRANSITIVE closure (review round 3): A calls B calls C;
    # change only the LEAF C to grow the collective — the divergent
    # call in UNCHANGED A is indicted through two summary hops, so the
    # gate must pull both B's and A's files
    git("add", "-A")
    git("commit", "-qm", "divergent caller")
    write("tools/leaf.py", "def leaf(x):\n    return x\n")
    write("tools/mid.py",
          "from tools.leaf import leaf\n\n\n"
          "def middle(x):\n"
          "    return leaf(x)\n")
    write("tools/caller.py",
          "import jax\n\nfrom tools.mid import middle\n\n\n"
          "def top(x):\n"
          "    if jax.process_index() == 0:\n"
          "        return middle(x)\n"
          "    return x\n")
    os.remove(os.path.join(repo, "tools", "sync.py"))
    git("add", "-A")
    git("commit", "-qm", "clean chain")
    write("tools/leaf.py",
          "import jax\n\n\n"
          "def leaf(x):\n"
          "    return jax.lax.psum(x, 'data')\n")
    extra, _amb = summary_scope(repo, ["tools/leaf.py"])
    assert set(extra) >= {"tools/mid.py", "tools/caller.py"}
    rc = main(["--changed", "--root", repo])
    out = capsys.readouterr().out
    assert rc == 1, out
    assert "spmd-divergence" in out and "tools/caller.py" in out

    # (c) subset-resolution bias (review round): a SECOND sync_helper
    # makes the name ambiguous repo-wide — the full scan refuses to
    # resolve it, and the --changed subset (which only sees one
    # definition) must refuse too instead of emitting a phantom
    # finding tier-1 never shows
    write("tools/sync.py",
          "import jax\n\n\n"
          "def sync_helper(x):\n"
          "    return jax.lax.psum(x, 'data')\n")
    write("tools/caller.py",
          "import jax\n\nfrom tools.sync import sync_helper\n\n\n"
          "def top(x):\n"
          "    if jax.process_index() == 0:\n"
          "        return sync_helper(x)\n"
          "    return x\n")
    write("tools/leaf.py", "def leaf(x):\n    return x\n")
    write("tools/sync2.py",
          "def sync_helper(x):\n    return x\n")
    git("add", "-A")
    git("commit", "-qm", "second helper: name now ambiguous")
    write("tools/caller.py",
          "import jax\n\nfrom tools.sync import sync_helper\n\n\n"
          "def top(x):\n"
          "    if jax.process_index() == 0:\n"
          "        return sync_helper(x)\n"
          "    return x + 0\n")
    _, ambiguous = summary_scope(repo, ["tools/caller.py"])
    assert "sync_helper" in ambiguous
    rc = main(["--changed", "--root", repo])
    out = capsys.readouterr().out
    assert rc == 0, out  # matches the full scan's under-reach verdict
    assert "spmd-divergence" not in out


def test_cli_scoped_path_scans_use_the_ambiguity_fence(tmp_path,
                                                       capsys):
    """Review round 3: a path-scoped scan (`graftlint tools/sub`) is a
    subset scan too — a name defined twice repo-wide must not
    uniqueness-resolve just because the second definition's file sits
    outside the given paths (the full scan refuses, so the scoped scan
    must refuse too, or it emits phantom findings tier-1 never shows
    and the baseline can never grandfather)."""
    from tools.graftlint.__main__ import main
    repo = str(tmp_path / "r")
    os.makedirs(os.path.join(repo, "tools", "sub"))

    def write(rel, text):
        with open(os.path.join(repo, rel), "w") as f:
            f.write(text)

    write("tools/sub/helper.py",
          "import jax\n\n\n"
          "def sync_helper(x):\n"
          "    return jax.lax.psum(x, 'data')\n")
    write("tools/other.py", "def sync_helper(x):\n    return x\n")
    write("tools/sub/a.py",
          "import jax\n\nfrom tools.sub.helper import sync_helper\n\n\n"
          "def top(x):\n"
          "    if jax.process_index() == 0:\n"
          "        return sync_helper(x)\n"
          "    return x\n")
    # control: with BOTH definitions in the scan set the name is
    # natively ambiguous and nothing flags
    assert main(["--root", repo, "tools"]) == 0
    capsys.readouterr()
    rc = main(["--root", repo, "tools/sub"])    # scoped: fenced
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "spmd-divergence" not in out


def test_cli_scoped_scans_do_not_spam_stale_entries(capsys):
    """A rule- or path-scoped scan must neither fail on out-of-scope
    grandfathered findings nor misreport their entries as stale."""
    from tools.graftlint.__main__ import main
    for argv in (["--rules", "lock-discipline"], ["tools"]):
        rc = main(argv)
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "stale" not in out, out
