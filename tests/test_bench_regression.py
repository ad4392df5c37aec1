"""tools/bench_regression.py (ISSUE 7 satellite): the mechanical
BENCH-trajectory gate, exercised on checked-in fixtures under
tests/bench_fixtures/ (ok/ = latest inside the noise band, regress/ =
latest 20% below the median) and on the repo's own real BENCH_r*.json
trajectory."""

import json
import os
import subprocess
import sys

import pytest

from tools.bench_regression import (check_metric, load_rounds, render,
                                    run)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "bench_fixtures")


def test_load_rounds_sorted_and_both_shapes():
    rounds = load_rounds(os.path.join(FIXTURES, "ok"))
    assert [r for r, _ in rounds] == [1, 2, 3, 4]
    # r04 is bench.py's BARE result object (no "parsed" wrapper)
    assert rounds[-1][1]["value"] == 96000.0


def test_ok_trajectory_passes():
    rc, rows = run(os.path.join(FIXTURES, "ok"),
                   ["value", "transformer_pc_per_sec",
                    "int8_pc_per_sec"],
                   band=0.05, window=5, min_history=2, strict=False)
    assert rc == 0
    by = {r["metric"]: r for r in rows}
    assert by["value"]["status"] == "ok"
    # latest 96000 vs median(100000, 102000, 98000) = 100000
    assert by["value"]["baseline"] == 100000.0
    assert by["value"]["ratio"] == pytest.approx(0.96)
    # int8 appears in only ONE prior round -> not gated, never a pass
    # by omission that reads as a verdict
    assert by["int8_pc_per_sec"]["status"] == "skip"


def test_regression_fails_nonzero():
    rc, rows = run(os.path.join(FIXTURES, "regress"),
                   ["value", "transformer_pc_per_sec"],
                   band=0.05, window=5, min_history=2, strict=False)
    assert rc == 1
    by = {r["metric"]: r for r in rows}
    assert by["value"]["status"] == "REGRESSION"
    assert by["transformer_pc_per_sec"]["status"] == "ok"
    assert "REGRESSION" in render(rows)


def test_band_floor_widens_with_noisy_history():
    # history spread (MAD-based) wider than the flag floor must win:
    # a historically jittery metric should not page on normal jitter
    noisy = [(1, 100.0), (2, 140.0), (3, 60.0), (4, 95.0)]
    row = check_metric("m", noisy, 5, 70.0, band_floor=0.05,
                       min_history=2)
    assert row["band"] > 0.05
    assert row["status"] == "ok"  # inside the widened band
    tight = [(1, 100.0), (2, 101.0), (3, 99.0)]
    row = check_metric("m", tight, 4, 70.0, band_floor=0.05,
                       min_history=2)
    assert row["status"] == "REGRESSION"


def test_insufficient_history_skips_then_strict_errors():
    rc, rows = run(os.path.join(FIXTURES, "ok"), ["value"],
                   band=0.05, window=5, min_history=10, strict=False)
    assert rc == 0 and rows[0]["status"] == "skip"
    rc, _rows = run(os.path.join(FIXTURES, "ok"), ["value"],
                    band=0.05, window=5, min_history=10, strict=True)
    assert rc == 2


def test_empty_dir_is_usage_error(tmp_path):
    rc, rows = run(str(tmp_path), ["value"], band=0.05, window=5,
                   min_history=2, strict=False)
    assert rc == 2 and rows == []


def test_cli_exit_codes_and_json():
    r = subprocess.run(
        [sys.executable, "tools/bench_regression.py", "--dir",
         os.path.join(FIXTURES, "regress"), "--metrics", "value",
         "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert r.returncode == 1, r.stdout + r.stderr
    rows = json.loads(r.stdout)
    assert rows[0]["status"] == "REGRESSION"
    r = subprocess.run(
        [sys.executable, "tools/bench_regression.py", "--dir",
         os.path.join(FIXTURES, "ok"), "--metrics", "value"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr


def test_mixed_schema_history_gates_each_round_on_its_own_fields():
    """ISSUE 8 satellite: rounds predating the round-13 `sparse_*`
    fields must not crash the gate — they drop out of the sparse
    metric's history (gating there starts once 2+ rounds report it)
    while `value` stays gated across the whole trajectory; booleans
    and non-numeric placeholders never enter a series."""
    rc, rows = run(os.path.join(FIXTURES, "mixed"),
                   ["value", "sparse_pc_per_sec", "sparse_update_ms",
                    "sparse_update_fused"],
                   band=0.05, window=5, min_history=2, strict=False)
    assert rc == 0
    by = {r["metric"]: r for r in rows}
    # value: gated over ALL five rounds
    assert by["value"]["status"] == "ok"
    assert by["value"]["history_rounds"] == [1, 2, 3, 4]
    # sparse_pc_per_sec: only r03/r04 form history (r01/r02 predate it)
    assert by["sparse_pc_per_sec"]["status"] == "ok"
    assert by["sparse_pc_per_sec"]["history_rounds"] == [3, 4]
    # latest carries a non-numeric placeholder -> skip, not a crash,
    # and the note names the real cause (key present, value unusable)
    assert by["sparse_update_ms"]["status"] == "skip"
    assert by["sparse_update_ms"]["note"] == "non-numeric in latest round"
    # booleans are flags, not gauges -> never gated
    assert by["sparse_update_fused"]["status"] == "skip"


def test_mixed_schema_latest_predates_metric_skips():
    """A metric the LATEST round doesn't report is a skip even when
    old rounds had it (r05 lacks nothing here, so gate a phantom)."""
    rc, rows = run(os.path.join(FIXTURES, "mixed"),
                   ["sparse_update_unique_rows"],
                   band=0.05, window=5, min_history=2, strict=False)
    assert rc == 0
    assert rows[0]["status"] == "skip"
    assert rows[0]["note"] == "absent from latest round"


def test_default_metrics_include_sparse_gate():
    from tools.bench_regression import DEFAULT_METRICS
    assert "sparse_pc_per_sec" in DEFAULT_METRICS


def _write_driver_rounds(dest) -> None:
    """Five rounds in the shape the driver wraps a bench.py line in
    ({n, cmd, rc, tail, parsed}), with the key set growing the way
    bench.py's did: headline only, then the floor, then the int8 and
    transformer cells."""
    parsed = {"metric": "path-contexts/sec/chip"}
    grown = [
        {"value": 4700000.0},
        {"value": 6000000.0, "fwd_bwd_floor_pc_per_sec": 8400000.0},
        {"value": 6600000.0, "int8_pc_per_sec": 5300000.0,
         "transformer_pc_per_sec": 2300000.0},
        {"value": 6600000.0},
        {"value": 6700000.0, "fwd_bwd_floor_pc_per_sec": 8500000.0,
         "int8_pc_per_sec": 5400000.0},
    ]
    for n, new in enumerate(grown, start=1):
        parsed = dict(parsed, **new)
        with open(os.path.join(dest, f"BENCH_r0{n}.json"), "w") as f:
            json.dump({"n": n, "cmd": "python bench.py", "rc": 0,
                       "tail": json.dumps(parsed) + "\n",
                       "parsed": parsed}, f)


def test_repo_trajectory_is_loadable(tmp_path):
    """A BENCH_r*.json history in the driver's wrapper shape stays
    parseable by the gate."""
    _write_driver_rounds(str(tmp_path))
    rounds = load_rounds(str(tmp_path))
    assert [r for r, _res in rounds] == [1, 2, 3, 4, 5]
    assert all("value" in res for _r, res in rounds)


# ---- --kind multichip: the MULTICHIP_r*.json trajectory (round 14)


def test_multichip_ok_trajectory_passes():
    """r01 is a seed-shaped failure record ({rc, ok, tail} — no
    metrics): skipped, never fatal; r02-r04 gate scaling_efficiency
    and multi_pc_per_sec with the latest inside the band."""
    rc, rows = run(os.path.join(FIXTURES, "multichip", "ok"),
                   ["scaling_efficiency", "multi_pc_per_sec"],
                   band=0.05, window=5, min_history=2, strict=False,
                   pattern="MULTICHIP_r*.json")
    assert rc == 0
    assert [r["status"] for r in rows] == ["ok", "ok"]
    # the failure-shape round contributed no history rows
    assert all(1 not in r["history_rounds"] for r in rows)


def test_multichip_efficiency_regression_fails():
    """A scaling-efficiency drop (0.87 -> 0.70) trips the gate even
    when absolute multi-leg throughput stays inside the band — the
    ratio is the pod-health headline."""
    rc, rows = run(os.path.join(FIXTURES, "multichip", "regress"),
                   ["scaling_efficiency", "multi_pc_per_sec"],
                   band=0.05, window=5, min_history=2, strict=False,
                   pattern="MULTICHIP_r*.json")
    assert rc == 1
    by = {r["metric"]: r for r in rows}
    assert by["scaling_efficiency"]["status"] == "REGRESSION"
    assert by["multi_pc_per_sec"]["status"] == "ok"


def test_multichip_cli_kind_selects_pattern_and_metrics():
    r = subprocess.run(
        [sys.executable, "tools/bench_regression.py", "--kind",
         "multichip", "--dir",
         os.path.join(FIXTURES, "multichip", "regress"), "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert r.returncode == 1, r.stdout + r.stderr
    rows = json.loads(r.stdout)
    assert {row["metric"] for row in rows} == {
        "scaling_efficiency", "multi_pc_per_sec",
        "recovery_steps_lost", "recovery_seconds",
        "host_skew_ratio"}


def test_multichip_recovery_metrics_gate_lower_is_better():
    """ISSUE 13 satellite: the kill-mid-run recovery costs gate with
    the band flipped into a CEILING — ok/ fixtures keep the latest
    inside it, regress/ blows recovery_seconds past it while the
    steps-lost series stays flat."""
    rc, rows = run(os.path.join(FIXTURES, "multichip", "ok"),
                   ["recovery_steps_lost", "recovery_seconds"],
                   band=0.05, window=5, min_history=2, strict=False,
                   pattern="MULTICHIP_r*.json")
    assert rc == 0
    assert [r["status"] for r in rows] == ["ok", "ok"]
    assert all(r["lower_is_better"] for r in rows)

    rc, rows = run(os.path.join(FIXTURES, "multichip", "regress"),
                   ["recovery_steps_lost", "recovery_seconds"],
                   band=0.05, window=5, min_history=2, strict=False,
                   pattern="MULTICHIP_r*.json")
    assert rc == 1
    by = {r["metric"]: r for r in rows}
    assert by["recovery_seconds"]["status"] == "REGRESSION"
    assert by["recovery_steps_lost"]["status"] == "ok"


def test_lower_is_better_direction_flips_the_band():
    """check_metric's direction logic: a DROP in a lower-is-better
    metric is never a regression (it's the improvement), a rise past
    the banded ceiling is; the same values under a higher-is-better
    metric read the opposite way."""
    hist = [(1, 30.0), (2, 31.0)]
    worse = check_metric("recovery_seconds", hist, 3, 80.0,
                         band_floor=0.05, min_history=2)
    assert worse["status"] == "REGRESSION" and worse["lower_is_better"]
    assert worse["floor"] > worse["baseline"]  # a ceiling, not a floor
    better = check_metric("recovery_seconds", hist, 3, 5.0,
                          band_floor=0.05, min_history=2)
    assert better["status"] == "ok"
    # same numbers, throughput-style metric: the 5.0 IS the regression
    throughput = check_metric("multi_pc_per_sec", hist, 3, 5.0,
                              band_floor=0.05, min_history=2)
    assert throughput["status"] == "REGRESSION"
    assert not throughput["lower_is_better"]


def test_lower_is_better_zero_baseline_still_gates():
    """A perfect-recovery history (baseline 0) must keep gating a
    cost metric — 0 is the BEST possible baseline there, not broken
    data (the throughput-metric skip rule stays)."""
    hist = [(1, 0.0), (2, 0.0)]
    worse = check_metric("recovery_steps_lost", hist, 3, 50.0,
                         band_floor=0.05, min_history=2)
    assert worse["status"] == "REGRESSION"
    assert worse["ratio"] is None  # undefined over a 0 baseline
    assert "—" in render([worse])  # and renders without crashing
    perfect = check_metric("recovery_steps_lost", hist, 3, 0.0,
                           band_floor=0.05, min_history=2)
    assert perfect["status"] == "ok"
    # a zero-baseline THROUGHPUT series is still broken data -> skip
    thr = check_metric("multi_pc_per_sec", hist, 3, 50.0,
                       band_floor=0.05, min_history=2)
    assert thr["status"] == "skip"


def test_multichip_default_metrics_include_recovery_gate():
    from tools.bench_regression import MULTICHIP_METRICS
    assert "recovery_steps_lost" in MULTICHIP_METRICS
    assert "recovery_seconds" in MULTICHIP_METRICS
    assert "host_skew_ratio" in MULTICHIP_METRICS


def test_multichip_host_skew_gates_lower_is_better():
    """ISSUE 17 satellite: the cohort-evenness ratio (worst member
    step p50 / cohort median) gates with the band flipped into a
    ceiling — ok/ keeps the latest skew (1.05) inside it, regress/
    jumps to 1.42 (one straggler host taxing every lock-step
    all-reduce) and fails even though the recovery pair stays flat."""
    rc, rows = run(os.path.join(FIXTURES, "multichip", "ok"),
                   ["host_skew_ratio"],
                   band=0.05, window=5, min_history=2, strict=False,
                   pattern="MULTICHIP_r*.json")
    assert rc == 0
    assert rows[0]["status"] == "ok" and rows[0]["lower_is_better"]

    rc, rows = run(os.path.join(FIXTURES, "multichip", "regress"),
                   ["host_skew_ratio", "recovery_steps_lost"],
                   band=0.05, window=5, min_history=2, strict=False,
                   pattern="MULTICHIP_r*.json")
    assert rc == 1
    by = {r["metric"]: r for r in rows}
    assert by["host_skew_ratio"]["status"] == "REGRESSION"
    assert by["recovery_steps_lost"]["status"] == "ok"


def test_multichip_repo_trajectory_accepted():
    """The REAL repo-root MULTICHIP history must never crash the gate:
    the seed rounds are failure records; once multichip_bench captures
    a real round, it becomes the gated latest. Before that, rc=2 (no
    result-carrying rounds) — either way, no exception and no false
    REGRESSION."""
    rc, rows = run(REPO, ["scaling_efficiency"], band=0.05, window=5,
                   min_history=2, strict=False,
                   pattern="MULTICHIP_r*.json")
    assert rc in (0, 2)
    assert all(r["status"] != "REGRESSION" for r in rows)


def test_bench_r06_with_phase_breakdown_passes_real_trajectory(tmp_path):
    """ISSUE 15 satellite (the round-13 TODO that keeps the trajectory
    gate alive): a BENCH_r06 carrying the new phase_* breakdown must
    pass the DEFAULT gate against five driver-shaped rounds without
    them — the new keys have no history yet (skip, by the mixed-schema
    rule) and the headline metrics gate on-trajectory values."""
    from tools.bench_regression import DEFAULT_METRICS
    _write_driver_rounds(str(tmp_path))
    r06 = {"metric": "path-contexts/sec/chip", "value": 6700000.0,
           "fwd_bwd_floor_pc_per_sec": 8500000.0,
           "int8_pc_per_sec": 5400000.0,
           "transformer_pc_per_sec": 2300000.0,
           "sparse_pc_per_sec": 8400000.0,
           "phase_embed_gather_ms": 4.1, "phase_concat_dense_ms": 3.0,
           "phase_forward_pool_ms": 5.2, "phase_backward_ms": 9.0,
           "phase_table_apply_ms": 6.4, "phase_sum_ms": 27.7}
    (tmp_path / "BENCH_r06.json").write_text(json.dumps(r06))
    rc, rows = run(str(tmp_path), list(DEFAULT_METRICS), band=0.05,
                   window=5, min_history=2, strict=False)
    assert rc == 0
    by = {r["metric"]: r for r in rows}
    assert by["value"]["status"] == "ok"
    # phase keys: no prior history -> skipped this round, gated from
    # the first round with 2+ phase-bearing predecessors
    assert by["phase_backward_ms"]["status"] == "skip"


# ---- --kind serving: the SERVING_r*.json trajectory (ISSUE 18)


def test_serving_ok_trajectory_passes():
    """serving_p99_ms gates as a CEILING (lower-is-better) and
    serving_req_per_sec as the usual floor; the ok/ trajectory keeps
    the latest round inside both bands."""
    rc, rows = run(os.path.join(FIXTURES, "serving", "ok"),
                   ["serving_p99_ms", "serving_req_per_sec"],
                   band=0.05, window=5, min_history=2, strict=False,
                   pattern="SERVING_r*.json")
    assert rc == 0
    by = {r["metric"]: r for r in rows}
    assert by["serving_p99_ms"]["status"] == "ok"
    assert by["serving_p99_ms"]["lower_is_better"]
    assert by["serving_req_per_sec"]["status"] == "ok"
    assert not by["serving_req_per_sec"]["lower_is_better"]


def test_serving_regression_fails_both_directions():
    """regress/ blows the p99 ceiling (19.5 vs a ~7.3 baseline) AND
    drops throughput below the floor — both read REGRESSION, each in
    its own direction."""
    rc, rows = run(os.path.join(FIXTURES, "serving", "regress"),
                   ["serving_p99_ms", "serving_req_per_sec"],
                   band=0.05, window=5, min_history=2, strict=False,
                   pattern="SERVING_r*.json")
    assert rc == 1
    by = {r["metric"]: r for r in rows}
    assert by["serving_p99_ms"]["status"] == "REGRESSION"
    assert by["serving_req_per_sec"]["status"] == "REGRESSION"


def test_serving_cli_kind_selects_pattern_and_metrics():
    r = subprocess.run(
        [sys.executable, "tools/bench_regression.py", "--kind",
         "serving", "--dir",
         os.path.join(FIXTURES, "serving", "regress"), "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert r.returncode == 1, r.stdout + r.stderr
    rows = json.loads(r.stdout)
    assert {row["metric"] for row in rows} == {
        "serving_p99_ms", "serving_req_per_sec"}


def test_serving_repo_trajectory_accepted():
    """The repo-root SERVING history must never crash the gate: with
    a single captured round there is no baseline yet (skip / rc 0);
    as rounds accrue it becomes a real gate. No false REGRESSION
    either way."""
    rc, rows = run(REPO, ["serving_p99_ms", "serving_req_per_sec"],
                   band=0.05, window=5, min_history=2, strict=False,
                   pattern="SERVING_r*.json")
    assert rc in (0, 2)
    assert all(r["status"] != "REGRESSION" for r in rows)


# ---- the gate on a round file's phase_*_ms keys ----

def test_bench_regression_catches_single_phase_2x():
    """Acceptance: the injected single-phase 2x regression fixture
    exits 1 under the default (phase-gated) metric set while the
    headline-only check would have passed."""
    from tools.bench_regression import DEFAULT_METRICS, run
    fixture = os.path.join(REPO, "tests", "bench_fixtures",
                           "phase_regress")
    rc, rows = run(fixture, list(DEFAULT_METRICS), band=0.05,
                   window=5, min_history=2, strict=False)
    assert rc == 1
    by = {r["metric"]: r for r in rows}
    assert by["phase_backward_ms"]["status"] == "REGRESSION"
    assert by["phase_backward_ms"]["lower_is_better"] is True
    assert by["value"]["status"] == "ok"
    # headline-only: the regression sails through — the reason the
    # per-phase gate exists
    rc2, _ = run(fixture, ["value", "sparse_pc_per_sec"], band=0.05,
                 window=5, min_history=2, strict=False)
    assert rc2 == 0


def test_bench_regression_gates_unlisted_phase_keys(tmp_path):
    """A phase key OUTSIDE the PHASE_MS_METRICS literals (a future
    mesh capture's phase_allreduce_ms, the int8 backward_apply
    remainder) is auto-discovered from the rounds and gated
    lower-is-better — no phase escapes the gate the docs promise."""
    from tools.bench_regression import run
    base = {"metric": "path-contexts/sec/chip", "value": 6.6e6,
            "phase_backward_apply_ms": 8.0}
    for n in (1, 2, 3):
        (tmp_path / f"BENCH_r0{n}.json").write_text(json.dumps(base))
    bad = dict(base)
    bad["phase_backward_apply_ms"] = 16.0  # 2x, headline steady
    (tmp_path / "BENCH_r04.json").write_text(json.dumps(bad))
    rc, rows = run(str(tmp_path), ["value"], band=0.05, window=5,
                   min_history=2, strict=False, auto_phases=True)
    assert rc == 1
    by = {r["metric"]: r for r in rows}
    assert by["phase_backward_apply_ms"]["status"] == "REGRESSION"
    assert by["value"]["status"] == "ok"
    # an explicit metric list is respected as given (the CLI passes
    # auto_phases only for default-set runs)
    rc2, _ = run(str(tmp_path), ["value"], band=0.05, window=5,
                 min_history=2, strict=False)
    assert rc2 == 0


def test_bench_regression_phase_direction_is_lower_better():
    from tools.bench_regression import (PHASE_MS_METRICS,
                                        _lower_is_better)
    for m in PHASE_MS_METRICS:
        assert _lower_is_better(m)
    assert _lower_is_better("recovery_seconds")
    assert not _lower_is_better("value")
    assert not _lower_is_better("phase_sum_bytes")
