#!/usr/bin/env python3
"""Cut a `.xplane.pb` of a benchmark run down to a test's recorded trace:

    python3 benchmark/tests/record_excerpt.py <run.xplane.pb> <out.json> [steps] [devices]

keeps the device operations and `bench/*` host spans that start inside
the first `steps` steps after the window opens (on at most `devices`
devices), as `trace_reduce.from_events` takes them, with what
`trace_reduce` makes of them beside, so that a change to the reduction
shows as a changed number."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import trace_reduce as tr  # noqa: E402


def main() -> int:
    import jax

    path, out = sys.argv[1], sys.argv[2]
    steps = int(sys.argv[3]) if len(sys.argv) > 3 else 2
    max_devices = int(sys.argv[4]) if len(sys.argv) > 4 else 2
    data = jax.profiler.ProfileData.from_file(path)
    events = []
    for plane in data.planes:
        for line in plane.lines:
            device_line = plane.name.startswith("/device:") and \
                line.name in ("XLA Ops", "Async XLA Ops")
            for ev in line.events:
                if device_line or ev.name.startswith("bench/"):
                    events.append({"plane": plane.name, "line": line.name,
                                   "name": ev.name[:1500],
                                   "start_ns": ev.start_ns,
                                   "dur_ns": ev.duration_ns, "stats": {}})
    window = next(e for e in events if e["name"] == "bench/window")
    dispatches = sorted(e["start_ns"] for e in events
                        if e["name"] == "bench/dispatch"
                        and e["start_ns"] >= window["start_ns"])
    lo = window["start_ns"]
    # the host dispatches ahead of the device: keep `steps` steps' worth
    # of device time, at the window's mean step time
    hi = lo + steps * window["dur_ns"] // max(len(dispatches), 1)
    devices = sorted({e["plane"] for e in events
                      if e["plane"].startswith("/device:")})[:max_devices]
    first_op = min(e["start_ns"] for e in events
                   if e["plane"] in devices and e["start_ns"] >= lo)
    keep = []
    for e in events:
        if e["plane"] in devices:
            if first_op <= e["start_ns"] < first_op + (hi - lo):
                keep.append(e)
        elif e["name"] == "bench/window":
            keep.append(dict(e, dur_ns=first_op + (hi - lo) - lo))
        elif e["name"].startswith("bench/") and lo <= e["start_ns"] < hi:
            keep.append(e)
    window_s = (first_op + (hi - lo) - lo) * 1e-9
    trace = tr.from_events(keep)
    s = tr.summary(trace, window_s)
    coll = tr.collective_seconds(trace)
    rec = {"source": os.path.basename(path), "steps": steps,
           "window_s": window_s,
           "expect": {"devices": len(trace["devices"]),
                      "busy_s": s["busy_s"],
                      "classes": [c for c, _ in
                                  s["breakdown"]["device_ops"][:5]],
                      "collective_s": coll["seconds"],
                      "collective_exposed_s": coll["exposed_seconds"]},
           "events": keep}
    with open(out, "w") as f:
        json.dump(rec, f, separators=(",", ":"))
    print(json.dumps(rec["expect"]), len(keep), "events",
          os.path.getsize(out), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
