"""`trace_reduce.py`: the interval arithmetic on a hand-made trace, and
the whole reduction on a small recorded one (`recorded/*.json`: events of
a real run on the v5e as `trace_reduce.load` lists them, cut to a few
steps)."""

import glob
import json
import os
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(TESTS))

import trace_reduce as tr  # noqa: E402


def ev(plane, line, name, start_us, dur_us, **stats):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": start_us * 1000, "dur_ns": dur_us * 1000,
            "stats": stats}


# names as the v5e's profiler gives them: the HLO text
SCATTER = ("%fusion.8 = bf16[1301138,128]{1,0:T(8,128)(2,1)} fusion("
           "s32[1638400]{0:T(1024)S(1)} %get-tuple-element.4, "
           "bf16[1638400,128]{1,0:T(8,128)(2,1)} %bitcast.19, "
           "s32[1638400]{0:T(1024)S(1)} %broadcast_clamp_fusion.1, "
           "bf16[]{:T(256)} %constant.321), kind=kCustom, "
           "calls=%fused_computation")
GATHER = ("%fusion.10 = bf16[1638400,128]{1,0:T(8,128)(2,1)} fusion("
          "bf16[1301138,128]{1,0:T(8,128)(2,1)} %params__token_emb__.1, "
          "s32[1638400]{0:T(1024)S(1)} %broadcast_clamp_fusion.4), "
          "kind=kCustom, calls=%fused_computation.1.clone")
POOL = ("%jvp_jit_attention_pool_pallas__.1 = (f32[8192,384]{1,0:T(8,128)"
        "S(1)}, f32[8192,200]{1,0:T(8,128)}) custom-call(f32[8192,200,384]"
        "{2,1,0:T(8,128)} %get-tuple-element.9), "
        "custom_call_target=\"tpu_custom_call\"")


def hand_made():
    d0, d1, host = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
    ops = "XLA Ops"
    return tr.from_events([
        ev(host, "python3", "bench/window", 0, 1000),
        ev(host, "python3", "bench/infeed_wait", 0, 90),
        ev(host, "python3", "bench/dispatch", 90, 10),
        ev(host, "python3", "bench/final_wait", 600, 400),
        # device 0: busy 100-400, 450-600 (all-reduce 450-600, of which
        # 500-550 is overlapped by a fusion), 700-900
        ev(d0, ops, SCATTER, 100, 300),
        ev(d0, ops, "%all-reduce.11 = bf16[1301138,128]{1,0:T(8,128)(2,1)} "
           "all-reduce(bf16[1301138,128]{1,0:T(8,128)(2,1)} %fusion.8), "
           "replica_groups={{0,1}}", 450, 150),
        ev(d0, ops, "%fusion.9 = f32[8192,384]{1,0:T(8,128)} fusion("
           "f32[8192,384]{1,0:T(8,128)} %x), kind=kLoop, calls=%f", 500, 50),
        ev(d0, ops, POOL, 700, 200),
        ev(d0, "XLA Modules", "jit_step(123)", 100, 800),
        # a copy that runs beside the ops is no busy time
        ev(d0, "Async XLA Ops", "%copy-start.3 = (s32[8]{0}, s32[8]{0}, "
           "u32[]) copy-start(s32[8]{0} %r)", 0, 1000),
        # device 1: busy 100-300 only
        ev(d1, ops, SCATTER, 100, 200),
        # before the window: not counted
        ev(d0, ops, GATHER, -500, 100),
    ])


def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert tr.total(tr.subtract([(0, 3), (5, 6)], [(0, 6)])) == 0


def test_classes_are_stable_names():
    t = hand_made()
    classes = {o["cls"] for o in t["devices"]["/device:TPU:0"]}
    assert "scatter[1301138x128]" in classes
    assert "all-reduce[1301138x128]" in classes
    assert "attention_pool_pallas[8192x384]" in classes
    assert "fusion.kLoop[8192x384]" in classes
    assert tr.op_class(GATHER, {}) == "gather[1638400x128]"
    assert tr.op_class("dot_general.1", {}) == "dot_general[]"


def test_busy_idle_exposed_and_gaps():
    t = hand_made()
    busy = tr.busy_by_device(t)
    assert busy["/device:TPU:0"] == pytest.approx(650e-6)
    assert busy["/device:TPU:1"] == pytest.approx(200e-6)
    s = tr.summary(t, window_s=1000e-6)
    assert s["busy_s"] == pytest.approx(425e-6)
    assert s["busiest_s"] == pytest.approx(650e-6)
    coll = tr.collective_seconds(t)              # mean over two devices
    assert coll["seconds"] == pytest.approx(150e-6 / 2)
    assert coll["exposed_seconds"] == pytest.approx(100e-6 / 2)
    gaps = tr.idle_gaps(t)
    # 0-100 under infeed_wait (90 of it), 400-450 under no span,
    # 600-700 and 900-1000 under final_wait
    assert gaps[0][1] == pytest.approx(100e-6)
    assert {g[0] for g in gaps} == {"bench/infeed_wait", "no bench span",
                                    "bench/final_wait"}
    secs = tr.class_seconds(t, lambda cls, name: cls.startswith("scatter"))
    assert secs == {"scatter[1301138x128]": pytest.approx(250e-6)}


def test_no_device_operation_is_an_error():
    t = tr.from_events([ev("/host:CPU", "python3", "bench/window", 0, 10)])
    with pytest.raises(RuntimeError):
        tr.summary(t, 1e-5)


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(TESTS, "recorded", "*.json"))))
def test_recorded_trace(path):
    with open(path) as f:
        rec = json.load(f)
    t = tr.from_events(rec["events"])
    s = tr.summary(t, rec["window_s"])
    want = rec["expect"]
    assert len(t["devices"]) == want["devices"]
    assert s["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert 0 < s["busy_s"] <= s["window_s"]
    top = [name for name, _ in s["breakdown"]["device_ops"]]
    for cls in want["classes"]:
        assert cls in top, (cls, top)
    coll = tr.collective_seconds(t)
    assert coll["exposed_seconds"] <= coll["seconds"] + 1e-12
    assert coll["seconds"] == pytest.approx(want["collective_s"], rel=1e-6,
                                            abs=1e-12)
