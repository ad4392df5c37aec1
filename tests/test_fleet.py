"""Fleet plane (ISSUE 17): the cohort collector's policy layer under
fake clocks and injected fetch — offset estimation with asymmetric
round trips, restart re-handshake, straggler attribution, cross-host
divergence, summed throughput, the JSONL ring — plus the real-socket
seams (/clock commit -> manifest, /fleet 404 without a collector) and
the measured-offset trace merge that retires the clock_note caveat.

House rules under test: every policy case is sleep-free and
socket-free (clock/wall/fetch injectable); members are REAL memory
registries rendered through the REAL exposition renderer, so the
parse side exercises the same text a live member serves. The
2-process end-to-end (slow-marked, chaos-recipe style) drives the
acceptance path: an `infeed/produce` sleep fault on one member flips
the cohort_straggler ticket through the supervisor's alert engine,
a mid-train /fleet scrape shows the cohort, and the post-run
`trace_report --merge` aligns on COMMITTED offsets.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from code2vec_tpu import obs
from code2vec_tpu.obs.exposition import render_prometheus
from code2vec_tpu.obs.fleet import FleetCollector, fleet_alert_rules


# ---- fakes -----------------------------------------------------------

class FakeClock:
    """One mutable timebase standing in for the collector's monotonic
    AND wall clocks (tests only care about deltas and offsets)."""

    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    wall = __call__


class FakeCohort:
    """In-memory member endpoints behind an injectable fetch: real
    registries, the real /metrics renderer, fake member clocks, zero
    sockets. `legs` per member is a list of (request_s, response_s)
    pairs consumed by successive /clock reads — each leg advances the
    shared clock, so round-trip asymmetry is exact and deterministic."""

    def __init__(self, clock: FakeClock):
        self.clock = clock
        self.members = {}
        self.commits = []  # (endpoint, query) per commit round trip

    def add(self, endpoint, tele, *, run_id, process_index=0,
            offset_s=0.0, legs=None):
        self.members[endpoint] = {
            "tele": tele, "run_id": run_id,
            "process_index": process_index, "offset_s": offset_s,
            "legs": list(legs or [])}

    def fetch(self, url):
        endpoint, _, path = url.split("://", 1)[1].partition("/")
        m = self.members[endpoint]
        path, _, query = path.partition("?")
        if path == "clock":
            if "commit=1" in query:
                self.commits.append((endpoint, query))
                return json.dumps({"committed": True})
            a, b = (m["legs"].pop(0) if m["legs"] else (0.0, 0.0))
            self.clock.t += a  # request leg
            body = {"mono": 0.0,
                    "wall": self.clock.t + m["offset_s"],
                    "identity": {"run_id": m["run_id"],
                                 "process_index": m["process_index"]}}
            self.clock.t += b  # response leg
            return json.dumps(body)
        if path == "vars":
            return json.dumps({"identity": {
                "run_id": m["run_id"],
                "process_index": m["process_index"]}})
        if path == "metrics":
            return render_prometheus(m["tele"])
        raise ValueError(url)


def _collector(clock, cohort, endpoints, **kw):
    kw.setdefault("handshake_samples", 3)
    return FleetCollector(
        obs.Telemetry.memory("sup").make_threadsafe(),
        members=endpoints, clock=clock, wall=clock.wall,
        fetch=cohort.fetch, **kw)


def _member_tele(step_ms=None, **counts):
    t = obs.Telemetry.memory("member").make_threadsafe()
    for name, v in counts.items():
        t.count(f"train/{name}", v)
    if step_ms is not None:
        t.record_ms("train/step_ms", step_ms)
    return t


# ---- clock handshake -------------------------------------------------

def test_offset_median_survives_asymmetric_round_trips():
    """One slow request leg and one slow response leg bias their
    samples in OPPOSITE directions (+/- (a-b)/2); the median of three
    lands exactly on the true offset, where a mean would not."""
    clk = FakeClock()
    cohort = FakeCohort(clk)
    cohort.add("m0:1", _member_tele(steps=3), run_id="r1",
               offset_s=5.0,
               legs=[(0.001, 0.001), (0.010, 0.002), (0.002, 0.010)])
    fc = _collector(clk, cohort, ["m0:1"])
    agg = fc.sample()
    row = agg["hosts"][0]
    assert row["up"] and row["run_id"] == "r1"
    assert row["clock_offset_s"] == pytest.approx(5.0, abs=1e-12)
    assert row["clock_committed"] is True
    # the measurement went BACK to the member for manifest persistence
    assert len(cohort.commits) == 1
    ep, query = cohort.commits[0]
    assert ep == "m0:1"
    assert "offset_s=5.000000000" in query and "samples=3" in query


def test_restart_rehandshakes_and_resets_rates():
    """A changed run_id means a relaunched process: fresh clock
    measurement (a new process is a new clock relationship) and a
    rate-window reset, so counters restarting from zero never render
    as negative throughput."""
    clk = FakeClock()
    cohort = FakeCohort(clk)
    cohort.add("m0:1", _member_tele(steps=100, examples=3200),
               run_id="r1", offset_s=1.0)
    fc = _collector(clk, cohort, ["m0:1"])
    fc.sample()
    clk.t += 1.0
    fc.sample()  # second sweep: rates flow, no re-handshake
    assert len(cohort.commits) == 1
    assert fc.aggregate()["hosts"][0]["steps_s"] == pytest.approx(0.0)

    # relaunch: new run_id, counters back near zero, new clock skew
    cohort.members["m0:1"].update(
        tele=_member_tele(steps=2, examples=64), run_id="r2",
        offset_s=-3.0)
    clk.t += 1.0
    row = fc.sample()["hosts"][0]
    assert len(cohort.commits) == 2  # re-handshake committed
    assert row["run_id"] == "r2"
    assert row["clock_offset_s"] == pytest.approx(-3.0)
    # reset window: first post-restart sweep has no prior to rate from
    assert row["steps_s"] is None


def test_member_down_is_a_row_not_an_exception():
    def dead(_url):
        raise OSError("connection refused")

    clk = FakeClock()
    fc = FleetCollector(obs.Telemetry.memory("sup").make_threadsafe(),
                        members=["gone:9"], clock=clk, wall=clk.wall,
                        fetch=dead)
    agg = fc.sample()
    assert agg["hosts"][0] == {"endpoint": "gone:9", "up": False,
                               "error": "connection refused"}
    assert agg["cohort"]["hosts_up"] == 0
    assert agg["cohort"]["hosts_total"] == 1


# ---- straggler attribution ------------------------------------------

def test_straggler_score_attributes_worst_series():
    """Host 2 is 3x the cohort median on step_ms but 4x on the
    exposed-allreduce phase: the score takes the worst ratio and the
    attribution names the series — `phase_allreduce_exposed`, not a
    mystery step-time number."""
    clk = FakeClock()
    cohort = FakeCohort(clk)
    for i, (step, phase) in enumerate(((100.0, 10.0), (100.0, 10.0),
                                       (300.0, 40.0))):
        t = _member_tele(step_ms=step, steps=10)
        t.record_ms("train/phase_allreduce_exposed_ms", phase)
        cohort.add(f"m{i}:1", t, run_id=f"r{i}", process_index=i)
    fc = _collector(clk, cohort, ["m0:1", "m1:1", "m2:1"])
    engine = obs.AlertEngine.create(
        fc.telemetry, mode="warn", rules=fleet_alert_rules())
    fc.attach(alerts=engine)
    agg = fc.sample()
    c = agg["cohort"]
    assert c["straggler_host"] == "m2:1"
    assert c["straggler_score"] == pytest.approx(4.0)
    assert c["straggler_series"] == "phase_allreduce_exposed"
    assert c["step_p50_skew"] == pytest.approx(3.0)
    rows = [r for r in agg["hosts"] if r["endpoint"] != "m2:1"]
    assert all(r["straggler_score"] == pytest.approx(1.0)
               for r in rows)
    # the gauges landed in the hosting registry and the ticket fired
    # through the attached engine in the SAME sweep
    assert fc.telemetry.gauges["fleet/straggler_score"] == \
        pytest.approx(4.0)
    state = {r["rule"]: r["state"] for r in engine.status_table()}
    assert state["cohort_straggler"] == "firing"
    assert state["cohort_divergence"] != "firing"


def test_single_host_has_no_straggler():
    """Skew needs a cohort: one host never gets a score (a median of
    itself is a tautology, not a signal)."""
    clk = FakeClock()
    cohort = FakeCohort(clk)
    cohort.add("m0:1", _member_tele(step_ms=100.0, steps=1),
               run_id="r1")
    fc = _collector(clk, cohort, ["m0:1"])
    c = fc.sample()["cohort"]
    assert c["straggler_score"] is None
    assert c["step_p50_skew"] is None


# ---- divergence ------------------------------------------------------

def _loss_member(cohort, endpoint, run_id, step, loss, digest=None):
    t = _member_tele(steps=step)
    t.gauge("train/loss", loss, emit=False)
    t.gauge("train/loss_step", float(step), emit=False)
    if digest is not None:
        t.gauge("train/params_digest", digest, emit=False)
        t.gauge("train/params_digest_step", float(step), emit=False)
    if endpoint in cohort.members:
        cohort.members[endpoint]["tele"] = t
    else:
        cohort.add(endpoint, t, run_id=run_id)
    return t


def test_divergence_fires_on_matching_step_disagreement():
    clk = FakeClock()
    cohort = FakeCohort(clk)
    _loss_member(cohort, "m0:1", "r0", 10, 0.5)
    _loss_member(cohort, "m1:1", "r1", 10, 0.5)
    fc = _collector(clk, cohort, ["m0:1", "m1:1"])
    engine = obs.AlertEngine.create(
        fc.telemetry, mode="warn", rules=fleet_alert_rules())
    fc.attach(alerts=engine)
    c = fc.sample()["cohort"]
    assert c["divergence"] == 0
    assert c["loss_divergence_rel"] == pytest.approx(0.0)

    # same step, different loss: the SPMD contract broke at runtime
    clk.t += 1.0
    _loss_member(cohort, "m0:1", "r0", 20, 0.5)
    _loss_member(cohort, "m1:1", "r1", 20, 0.6)
    c = fc.sample()["cohort"]
    assert c["divergence"] == 1
    assert c["loss_divergence_step"] == 20
    assert c["loss_divergence_rel"] == pytest.approx(0.1 / 0.55,
                                                     rel=1e-6)
    state = {r["rule"]: r["state"] for r in engine.status_table()}
    assert state["cohort_divergence"] == "firing"


def test_divergence_params_digest_channel():
    """Loss can agree while weights drift (a buggy non-replicated
    optimizer state): the sampled params fingerprint is its own
    channel, matched at its own step labels."""
    clk = FakeClock()
    cohort = FakeCohort(clk)
    _loss_member(cohort, "m0:1", "r0", 10, 0.5, digest=1234.5)
    _loss_member(cohort, "m1:1", "r1", 10, 0.5, digest=1240.5)
    fc = _collector(clk, cohort, ["m0:1", "m1:1"])
    c = fc.sample()["cohort"]
    assert c["loss_divergence_rel"] == pytest.approx(0.0)
    assert c["params_digest_divergence_rel"] > 1e-4
    assert c["params_digest_divergence_step"] == 10
    assert c["divergence"] == 1


def test_disjoint_steps_never_compare():
    """Hosts scraped at different steps with no overlap: nothing to
    compare, no false alarm."""
    clk = FakeClock()
    cohort = FakeCohort(clk)
    _loss_member(cohort, "m0:1", "r0", 10, 0.5)
    _loss_member(cohort, "m1:1", "r1", 11, 0.9)
    fc = _collector(clk, cohort, ["m0:1", "m1:1"])
    c = fc.sample()["cohort"]
    assert c["divergence"] == 0
    assert c["loss_divergence_step"] is None


# ---- throughput, history, reads -------------------------------------

def test_cohort_throughput_sums_and_persists(tmp_path):
    clk = FakeClock()
    cohort = FakeCohort(clk)
    t0 = _member_tele(steps=10, examples=0)
    t0.gauge("train/max_contexts", 8, emit=False)
    t1 = _member_tele(steps=10, examples=0)
    t1.gauge("train/max_contexts", 8, emit=False)
    cohort.add("m0:1", t0, run_id="r0")
    cohort.add("m1:1", t1, run_id="r1")
    hist = str(tmp_path / "fleet.jsonl")
    fc = _collector(clk, cohort, ["m0:1", "m1:1"], history_path=hist)
    fc.sample()  # first sweep primes the rate windows
    clk.t += 2.0
    t0.count("train/examples", 64)
    t1.count("train/examples", 32)
    agg = fc.sample()
    c = agg["cohort"]
    assert c["ex_per_sec"] == pytest.approx(48.0)
    # pc/s = ex/s * max_contexts, summed over the cohort
    assert c["pc_per_sec"] == pytest.approx(384.0)
    assert [r["pc_s"] for r in agg["hosts"]] == \
        [pytest.approx(256.0), pytest.approx(128.0)]
    # ring + JSONL: the aggregate IS the durable record
    assert len(fc.history) == 2 and fc.aggregate() is agg
    brief = fc.brief()
    assert brief["sweeps"] == 2
    assert [h["endpoint"] for h in brief["hosts"]] == ["m0:1", "m1:1"]
    fc.stop()
    lines = [json.loads(ln) for ln in
             open(hist, encoding="utf-8").read().splitlines()]
    assert len(lines) == 2
    assert lines[1]["cohort"]["pc_per_sec"] == pytest.approx(384.0)
    # prometheus rendering: cohort totals bare, per-host labeled
    prom = fc.render_prometheus()
    assert "fleet_pc_per_sec 384.0" in prom
    assert 'fleet_host_pc_per_sec{host="m0:1"} 256.0' in prom


def test_set_members_keeps_surviving_state():
    """An elastic resize re-points the scrape set; survivors keep
    their handshake (no gratuitous re-measure), dropped members
    leave."""
    clk = FakeClock()
    cohort = FakeCohort(clk)
    cohort.add("m0:1", _member_tele(steps=1), run_id="r0",
               offset_s=2.0)
    cohort.add("m1:1", _member_tele(steps=1), run_id="r1")
    fc = _collector(clk, cohort, ["m0:1", "m1:1"])
    fc.sample()
    assert len(cohort.commits) == 2
    fc.set_members(["m0:1"])  # shrink to the survivor
    clk.t += 1.0
    agg = fc.sample()
    assert [r["endpoint"] for r in agg["hosts"]] == ["m0:1"]
    assert len(cohort.commits) == 2  # survivor NOT re-handshaked
    assert agg["hosts"][0]["clock_offset_s"] == pytest.approx(2.0)


def test_disabled_path_is_the_shared_singleton():
    off = FleetCollector.create(obs.Telemetry.memory("x"), members=())
    assert off is FleetCollector.disabled()
    assert FleetCollector.create(
        obs.Telemetry.disabled(), members=["m:1"]) is off
    assert FleetCollector.create(None, members=["m:1"]) is off
    before = threading.enumerate()
    assert off.start() is off
    assert off.sample() == {} and off.aggregate() == {}
    assert off.brief() == {}
    off.set_members(["m:1"])
    off.stop()
    assert threading.enumerate() == before


# ---- real-socket seams ----------------------------------------------

def test_fleet_endpoint_404_without_collector():
    t = obs.Telemetry.memory("m").make_threadsafe()
    srv = obs.MetricsServer(t, port=0).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.bound_port}/fleet", timeout=5)
        assert e.value.code == 404
    finally:
        srv.stop()


def test_clock_commit_persists_manifest_block(tmp_path):
    """The member half of the handshake: a committed offset lands in
    the run manifest as the `clock` block trace_report --merge aligns
    with — fresh anchor pair, measured offset, sample count."""
    run = obs.Telemetry.create(str(tmp_path), component="train")
    srv = obs.MetricsServer(run, port=0,
                            identity={"run_id": run.run_id}).start()
    try:
        base = f"http://127.0.0.1:{srv.bound_port}"
        out = json.load(urllib.request.urlopen(
            base + "/clock?commit=1&offset_s=0.25&samples=5",
            timeout=5))
        assert out["committed"] is True
        manifest = json.load(
            open(os.path.join(run.run_dir, "manifest.json")))
        clock = manifest["clock"]
        assert clock["wall_offset_s"] == pytest.approx(0.25)
        assert clock["samples"] == 5
        assert isinstance(clock["mono"], float)
        assert isinstance(clock["wall"], float)
        # malformed commit: refused, manifest untouched
        bad = json.load(urllib.request.urlopen(
            base + "/clock?commit=1", timeout=5))
        assert bad["committed"] is False
    finally:
        srv.stop()
        run.close()


# ---- obs_top --fleet view -------------------------------------------

def test_obs_top_renders_fleet_aggregate():
    """`obs_top --fleet` renders the collector's aggregate — it never
    re-derives: cohort headline (summed pc/s, straggler + attributed
    series, converged/DIVERGED, clock spread) plus per-host rows with
    measured offsets and DOWN markers."""
    from tools import obs_top
    clk = FakeClock()
    cohort = FakeCohort(clk)
    for i, (step, phase) in enumerate(((100.0, 10.0), (300.0, 40.0))):
        t = _member_tele(step_ms=step, steps=7, examples=0)
        t.gauge("train/max_contexts", 8, emit=False)
        t.record_ms("train/phase_allreduce_exposed_ms", phase)
        cohort.add(f"m{i}:1", t, run_id=f"r{i}", offset_s=0.002 * i)
    fc = _collector(clk, cohort, ["m0:1", "m1:1"])
    fc.sample()
    clk.t += 1.0
    for m in cohort.members.values():
        m["tele"].count("train/examples", 32)
    agg = fc.sample()
    out = obs_top.render_fleet(agg)
    assert "2/2 hosts up" in out
    assert "pc/s (sum) 512.0" in out  # 2 hosts x 32 ex/s x C=8
    assert "(m1:1 via phase_allreduce_exposed)" in out
    assert "converged" in out and "DIVERGED" not in out
    assert "allreduce_exposed" in out  # phase table rides along
    # a dead member renders as a DOWN row, not a crash
    agg["hosts"][1] = {"endpoint": "m1:1", "up": False,
                       "error": "connection refused"}
    assert "DOWN: connection refused" in obs_top.render_fleet(agg)


def test_obs_top_fetch_fleet_normalizes_url():
    """fetch_fleet accepts host:port, a base URL, or the full /fleet
    URL — all land on the collector's endpoint."""
    from tools import obs_top
    t = obs.Telemetry.memory("sup").make_threadsafe()
    clk = FakeClock()
    cohort = FakeCohort(clk)
    cohort.add("m0:1", _member_tele(step_ms=50.0, steps=1),
               run_id="r0")
    fc = FleetCollector(t, members=["m0:1"], clock=clk, wall=clk.wall,
                        fetch=cohort.fetch, handshake_samples=1)
    fc.sample()
    srv = obs.MetricsServer(t, port=0, fleet=fc).start()
    try:
        for url in (f"127.0.0.1:{srv.bound_port}",
                    f"http://127.0.0.1:{srv.bound_port}/",
                    f"http://127.0.0.1:{srv.bound_port}/fleet"):
            agg = obs_top.fetch_fleet(url)
            assert agg["cohort"]["hosts_up"] == 1
    finally:
        srv.stop()


# ---- supervisor hosting ---------------------------------------------

def test_supervisor_hosts_collector_and_rules():
    from code2vec_tpu.training.supervisor import Supervisor
    sup = Supervisor(
        lambda *a: None, num_procs=1,
        telemetry=obs.Telemetry.memory("sup").make_threadsafe())
    # the cohort tickets ride the stock supervisor engine (quiet until
    # the fleet publishes: threshold rules on absent series never fire)
    rules = {r["rule"] for r in sup.alerts.status_table()}
    assert {"cohort_straggler", "cohort_divergence"} <= rules
    # null collector: attach is a no-op, topology stays fleet-free
    sup.attach_fleet(FleetCollector.disabled(), ["x:1"])
    assert sup.fleet is None
    assert "fleet" not in sup.cohort_topology()
    # live collector: cohort snapshot joins the stall-dump topology

    def dead(_url):
        raise OSError("down")

    clk = FakeClock()
    fc = FleetCollector(sup.telemetry, members=["m:1"], clock=clk,
                        wall=clk.wall, fetch=dead)
    sup.attach_fleet(fc, ["m:1"])
    assert sup.fleet is fc and fc._alerts is sup.alerts
    fc.sample()
    topo = sup.cohort_topology()
    assert topo["fleet"]["sweeps"] == 1
    assert topo["fleet"]["cohort"]["hosts_up"] == 0


# ---- measured-offset trace merge ------------------------------------

def _span(t0, name="train/step_cycle", trace="t", span="s"):
    return {"kind": "span", "trace": trace, "span": span,
            "name": name, "t0": t0, "dur_ms": 5.0, "tid": 1,
            "tname": "main", "attrs": {"step": 1}}


def _run_dir(d, pidx, created, spans, clock=None):
    manifest = {"run_id": f"run-p{pidx}", "component": "train",
                "process_index": pidx, "process_count": 2,
                "created_unix": created}
    if clock is not None:
        manifest["clock"] = clock
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    with open(os.path.join(d, "events.jsonl"), "w") as f:
        for e in spans:
            f.write(json.dumps(e) + "\n")
    return d


def test_merge_uses_committed_offsets_not_created_unix(tmp_path):
    """Two runs whose manifests carry handshake clock blocks: the
    merged timeline realigns each run's monotonic spans onto the
    collector's wall clock (`t0 - mono + wall - wall_offset_s`). The
    created_unix stamps are 2.5 s apart ON PURPOSE — the measured path
    must ignore them (true gap: 0.5 s) — and the clock_note caveat is
    retired."""
    from tools.trace_report import write_chrome_trace
    d0 = _run_dir(str(tmp_path / "r0"), 0, 1000.0,
                  [_span(100.0, trace="t0", span="s0")],
                  clock={"mono": 100.0, "wall": 1000.0,
                         "wall_offset_s": 0.0, "samples": 5})
    # p1's wall ran 2 s ahead; the handshake MEASURED that, so its
    # span (monotonic t0=50.5, 0.5 s after its anchor) lands 0.5 s
    # after p0's on the shared timeline
    d1 = _run_dir(str(tmp_path / "r1"), 1, 1002.5,
                  [_span(50.5, trace="t1", span="s1")],
                  clock={"mono": 50.0, "wall": 1002.0,
                         "wall_offset_s": 2.0, "samples": 5})
    out = str(tmp_path / "merged.json")
    write_chrome_trace([d0, d1], out, merge=True)
    trace = json.load(open(out))["traceEvents"]
    assert not [e for e in trace if e["name"] == "clock_note"]
    e0 = next(e for e in trace
              if e["name"] == "train/step_cycle" and e["pid"] == 0)
    e1 = next(e for e in trace
              if e["name"] == "train/step_cycle" and e["pid"] == 1)
    assert e1["ts"] - e0["ts"] == pytest.approx(0.5e6, abs=1.0)
    # process rows carry the measured offset for the reader
    names = {e["pid"]: e["args"] for e in trace
             if e["name"] == "process_name"}
    assert names[0]["clock_offset_s"] == pytest.approx(0.0)
    assert names[1]["clock_offset_s"] == pytest.approx(2.0)


def test_merge_half_measured_cohort_falls_back(tmp_path):
    """One run without a clock block poisons the measured path for the
    WHOLE merge (exact and sloppy timelines must not interleave as if
    comparable): created_unix fallback, clock_note caveat back on
    every process."""
    from tools.trace_report import write_chrome_trace
    d0 = _run_dir(str(tmp_path / "r0"), 0, 1000.0,
                  [_span(100.0, trace="t0", span="s0")],
                  clock={"mono": 100.0, "wall": 1000.0,
                         "wall_offset_s": 0.0})
    d1 = _run_dir(str(tmp_path / "r1"), 1, 1002.5,
                  [_span(50.5, trace="t1", span="s1")])
    out = str(tmp_path / "merged.json")
    write_chrome_trace([d0, d1], out, merge=True)
    trace = json.load(open(out))["traceEvents"]
    notes = [e for e in trace if e["name"] == "clock_note"]
    assert len(notes) == 2
    assert "fleet plane" in notes[0]["args"]["note"]
    e0 = next(e for e in trace
              if e["name"] == "train/step_cycle" and e["pid"] == 0)
    e1 = next(e for e in trace
              if e["name"] == "train/step_cycle" and e["pid"] == 1)
    assert e1["ts"] - e0["ts"] == pytest.approx(2.5e6, abs=1.0)


# ---- end to end: live 2-process cohort ------------------------------

@pytest.mark.slow
def test_live_cohort_straggler_ticket_and_merged_trace(tmp_path):
    """The ISSUE 17 acceptance path, 2-process Gloo cohort on CPU:
    an `infeed/produce` sleep fault on member 1 makes it the
    straggler; the supervisor-hosted collector measures it live, the
    cohort_straggler ticket flips through the supervisor's alert
    engine, a mid-train /fleet scrape shows per-host p50s + summed
    pc/s, and the post-run --merge trace aligns on the COMMITTED
    offsets (no clock_note)."""
    from code2vec_tpu.parallel.compat import free_port
    from code2vec_tpu.training.supervisor import (Supervisor,
                                                  build_cli_spawn)
    from tools import chaos
    from tools.telemetry_report import find_runs
    from tools.trace_report import write_chrome_trace

    prefix = chaos.build_dataset(str(tmp_path / "ds"))
    faults = str(tmp_path / "faults.json")
    chaos._write_faults(faults, {
        "infeed/produce": {"action": "sleep", "delay_ms": 150,
                           "times": -1, "process": 1}})
    members_dir = str(tmp_path / "members")
    # sync checkpointing: the loopback-Gloo transport race
    # reproduces deterministically
    # when the async writer thread's device work interleaves with a
    # cohort this skewed — verified pre-existing with the fault alone,
    # no fleet plane attached
    cmd = chaos.train_cmd(prefix, str(tmp_path / "ckpt"),
                          epochs=6) + \
        ["--telemetry_dir", members_dir, "--trace",
         "--faults", faults, "--async_checkpoint", "off"]
    ports = [free_port(), free_port()]
    members = [f"127.0.0.1:{p}" for p in ports]

    sup_tele = obs.Telemetry.memory("supervisor").make_threadsafe()
    sup = Supervisor(
        build_cli_spawn(cmd, num_procs=2,
                        out_dir=str(tmp_path / "logs"),
                        cpu_devices=1, metrics_ports=ports),
        num_procs=2, max_restarts=1, telemetry=sup_tele,
        attempt_timeout_s=600.0, log=lambda _m: None)
    fc = FleetCollector.create(sup_tele, members=members,
                               interval_s=0.25, handshake_samples=3)
    sup.attach_fleet(fc, members)
    fsrv = obs.MetricsServer(sup_tele, port=0, fleet=fc).start()
    fleet_url = f"http://127.0.0.1:{fsrv.bound_port}/fleet"

    rc_box = {}
    th = threading.Thread(
        target=lambda: rc_box.update(rc=sup.run()), daemon=True)
    best = {}
    ticket_fired = False
    th.start()
    try:
        deadline = time.time() + 570.0
        while th.is_alive() and time.time() < deadline:
            time.sleep(0.5)
            try:
                agg = json.load(
                    urllib.request.urlopen(fleet_url, timeout=5))
            except (OSError, ValueError):
                continue
            c = agg.get("cohort") or {}
            up = [r for r in agg.get("hosts", ()) if r.get("up")]
            if (c.get("hosts_up") == 2 and c.get("pc_per_sec")
                    and all(r.get("step_p50") is not None
                            for r in up)
                    and (c.get("straggler_score") or 0) >
                    (best.get("cohort", {})
                     .get("straggler_score") or 0)):
                best = agg
            ticket_fired = ticket_fired or any(
                r["rule"] == "cohort_straggler"
                and r["state"] == "firing"
                for r in sup.alerts.status_table())
        th.join(timeout=60.0)
    finally:
        fsrv.stop()
    assert rc_box.get("rc") == 0, "supervised cohort run failed"

    # one mid-train /fleet scrape showed the whole cohort: both
    # hosts' step p50s, summed path-context throughput, and the
    # injected slow member as THE straggler past the ticket line
    assert best, "never saw a full 2-host /fleet snapshot mid-train"
    c = best["cohort"]
    assert c["pc_per_sec"] > 0
    assert c["straggler_score"] > 1.5
    assert c["straggler_host"] == members[1]
    by_ep = {r["endpoint"]: r for r in best["hosts"]}
    assert {r["process_index"] for r in best["hosts"]} == {0, 1}
    assert all(r["clock_committed"] for r in best["hosts"])
    assert by_ep[members[1]]["straggler_score"] == \
        pytest.approx(c["straggler_score"])
    assert ticket_fired, "cohort_straggler never flipped the engine"

    # the committed offsets make the merged trace MEASURED: pick the
    # final attempt's run per process, align, and the caveat is gone
    runs = {}
    for d in find_runs(members_dir):
        m = json.load(open(os.path.join(d, "manifest.json")))
        if m.get("component") != "train" or "clock" not in m:
            continue
        p = m.get("process_index")
        if p not in runs or m.get("created_unix", 0) > runs[p][0]:
            runs[p] = (m.get("created_unix", 0), d)
    assert set(runs) == {0, 1}, f"missing committed runs: {runs}"
    out = str(tmp_path / "merged.json")
    write_chrome_trace([d for _, d in runs.values()], out, merge=True)
    trace = json.load(open(out))["traceEvents"]
    assert not [e for e in trace if e["name"] == "clock_note"]
    spans = [e for e in trace if e.get("cat") == "span"]
    pids = {e["pid"] for e in spans}
    assert pids == {0, 1}
    # consistent interleaving: the two processes' step timelines
    # overlap on the shared clock (they trained concurrently)
    span_rng = {p: (min(e["ts"] for e in spans if e["pid"] == p),
                    max(e["ts"] for e in spans if e["pid"] == p))
                for p in pids}
    assert span_rng[0][0] < span_rng[1][1]
    assert span_rng[1][0] < span_rng[0][1]
