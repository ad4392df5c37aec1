"""From a profiler trace (`.xplane.pb`) to what the per-layer readers
read: per device the intervals of its operations under stable class
names, the benchmark's own host spans (`bench/*`), busy and idle time,
the exposed part of collectives, and the longest idle gaps by the host
span they fall under.

Two steps, so that the arithmetic can be checked without a profiler:
`load(path)` turns the file into plain lists (`from_events` builds the
same from recorded events, which is what the tests keep), and everything
else works on those lists.

An operation's class is `<kind>[<shape>]`: the kind from the HLO text
the profiler names the operation by (`op_class`), the shape of its
result. `scatter[1301138x128]` stays
`scatter[1301138x128]` when the compiler renumbers `fusion.8`.
"""

from __future__ import annotations

import re
from typing import Dict, List, Tuple

Interval = Tuple[float, float]          # start, end, in seconds

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


# ---- loading ------------------------------------------------------------

_OP_LINES = ("XLA Ops", "Async XLA Ops")


def load(path: str) -> dict:
    """Only what the reduction reads is kept: the devices' operation
    lines and the `bench/*` host spans (a four-chip trace holds a hundred
    thousand runtime events beside them)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = list(data.planes)
    on_tpu = any(p.name.startswith("/device:") for p in planes)
    events = []
    for plane in planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in _OP_LINES:
                continue
            for ev in line.events:
                stats = {}
                if not device:
                    if not on_tpu:      # a CPU trace marks ops by a stat
                        stats = {k: v for k, v in ev.stats}
                    if not (ev.name.startswith("bench/")
                            or "hlo_op" in stats):
                        continue
                events.append({"plane": plane.name, "line": line.name,
                               "name": ev.name, "start_ns": ev.start_ns,
                               "dur_ns": ev.duration_ns, "stats": stats})
    return from_events(events)


def from_events(events: List[dict]) -> dict:
    """{"devices": {name: [op, ...]}, "spans": [span, ...]} where an op is
    {"name", "cls", "async", "start", "end"} (seconds) and a span {"name",
    "start", "end"}. An asynchronous operation (the profiler's `Async XLA
    Ops` line) is kept only where it is a collective: it counts as
    collective time and never as busy time."""
    on_chip = [e for e in events if e["plane"].startswith("/device:")
               and e["line"] in _OP_LINES]
    key = "plane"
    if not on_chip:
        # a CPU trace (the tests): XLA's CPU client marks its operations
        # with an `hlo_op` stat on its own thread's line
        on_chip = [e for e in events if "hlo_op" in e["stats"]]
        key = "line"
    devices: Dict[str, list] = {}
    for e in on_chip:
        if e["dur_ns"] <= 0:
            continue
        start = e["start_ns"] * 1e-9
        cls = op_class(e["name"], e["stats"])
        overlapped = e["line"] == "Async XLA Ops"
        if overlapped and not is_collective(cls):
            continue        # copies and slices that run beside the ops
        devices.setdefault(e[key], []).append({
            "name": e["name"], "cls": cls, "async": overlapped,
            "start": start, "end": start + e["dur_ns"] * 1e-9})
    for ops in devices.values():
        ops.sort(key=lambda o: o["start"])
    spans = [{"name": e["name"], "start": e["start_ns"] * 1e-9,
              "end": (e["start_ns"] + e["dur_ns"]) * 1e-9}
             for e in events if e["name"].startswith("bench/")]
    spans.sort(key=lambda s: s["start"])
    return {"devices": devices, "spans": spans}


_HLO = re.compile(r"^%?([\w.\-]+) = \(?([a-z]+[0-9a-z]*)\[([0-9,]*)\]")
_OPCODE = re.compile(r"\s([a-z][a-z\-]*)\(")
_OPERAND = re.compile(r"([a-z]+[0-9a-z]*)\[([0-9,]*)\]")


def op_class(name: str, stats: dict) -> str:
    """`<kind>[<dims of the result>]`, stable across compilations.

    On the TPU the profiler names an operation by its HLO text
    (`%fusion.6 = bf16[1301138,128]{..} fusion(s32[1638400]{..} %x,
    bf16[1638400,128]{..} %y, ..), kind=kCustom, calls=..`). The kind is
    the opcode; a fusion the compiler named keeps that name
    (`convolution_tanh_fusion`), a kernel its own (`attention_pool_pallas`),
    and an unnamed custom fusion is told by its shapes: with an index
    vector s32[N] among its operands, a result of N rows is a gather, and
    a result that another operand's N rows are written into is a
    scatter."""
    m = _HLO.match(name)
    if m is None:                       # a CPU trace: plain instruction names
        base = re.sub(r"[.:][0-9]+$", "", name.lstrip("%"))
        for word in _COLLECTIVES:
            if base.startswith(word):
                return f"{word}[]"
        return f"{base}[]"
    inst, dims = re.sub(r"\.[0-9]+$", "", m.group(1)), m.group(3)
    rest = name[m.end():]
    op = _OPCODE.search(rest)
    opcode = op.group(1) if op else inst
    kind = opcode
    for word in _COLLECTIVES:
        if opcode.startswith(word):
            kind = word
            break
    else:
        if opcode == "custom-call":
            kind = re.sub(r"^(jvp_|transpose_|jit_|_)+|(_+)$", "", inst)
        elif opcode == "fusion" and inst != "fusion":
            kind = inst
        elif opcode == "fusion":
            kind = "fusion"
            found = re.search(r"kind=(k[A-Za-z]+)", rest)
            operands = _OPERAND.findall(rest[:rest.find("), kind=")
                                             if "), kind=" in rest else None])
            index = [d for t, d in operands
                     if t.startswith("s") and d and "," not in d]
            rows = dims.split(",")[0] if dims else ""
            if index and "," in dims:
                if rows in index:
                    kind = "gather"
                elif any(d.split(",")[0] in index and "," in d
                         for _t, d in operands):
                    kind = "scatter"
            if kind == "fusion" and found:
                kind = "fusion." + found.group(1)
    return f"{kind}[{dims.replace(',', 'x')}]"


# ---- interval arithmetic ------------------------------------------------

def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The part of `a` (a union) that no interval of `b` (a union) covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(ops: list, lo: float, hi: float,
         with_async: bool = False) -> List[Interval]:
    return [(max(o["start"], lo), min(o["end"], hi)) for o in ops
            if o["end"] > lo and o["start"] < hi
            and (with_async or not o.get("async"))]


# ---- what the readers ask -----------------------------------------------

def window_of(trace: dict) -> Interval:
    """The measured window on the trace's clock: the `bench/window` span."""
    for s in trace["spans"]:
        if s["name"] == "bench/window":
            return s["start"], s["end"]
    ends = [(ops[0]["start"], ops[-1]["end"])
            for ops in trace["devices"].values() if ops]
    return min(s for s, _ in ends), max(e for _, e in ends)


def busy_by_device(trace: dict) -> Dict[str, float]:
    lo, hi = window_of(trace)
    return {d: total(union(clip(ops, lo, hi)))
            for d, ops in trace["devices"].items()}


def class_seconds(trace: dict, match=None) -> Dict[str, float]:
    """Seconds of device time by class inside the window, a mean over the
    devices. `match(cls, name) -> bool` keeps some operations only."""
    lo, hi = window_of(trace)
    out: Dict[str, float] = {}
    n = max(len(trace["devices"]), 1)
    for ops in trace["devices"].values():
        for o in ops:
            if o["end"] <= lo or o["start"] >= hi or o.get("async"):
                continue
            if match is not None and not match(o["cls"], o["name"]):
                continue
            out[o["cls"]] = out.get(o["cls"], 0.0) + (
                min(o["end"], hi) - max(o["start"], lo)) / n
    return out


def is_collective(cls: str) -> bool:
    return cls.split("[")[0].startswith(_COLLECTIVES)


def collective_seconds(trace: dict, kinds=None) -> Dict[str, float]:
    """Per device, averaged: seconds in which a collective ran (of the
    `kinds` given, e.g. ("all-reduce",); all of them by default), and the
    part of them in which nothing else ran on that device. A collective
    the compiler made asynchronous counts from its start to its done."""
    lo, hi = window_of(trace)
    ran = exposed = 0.0

    def wanted(o):
        return is_collective(o["cls"]) and (
            kinds is None or o["cls"].split("[")[0] in kinds)

    for ops in trace["devices"].values():
        coll = union(clip([o for o in ops if wanted(o)],
                          lo, hi, with_async=True))
        rest = union(clip([o for o in ops if not is_collective(o["cls"])],
                          lo, hi))
        ran += total(coll)
        exposed += total(subtract(coll, rest))
    n = max(len(trace["devices"]), 1)
    return {"seconds": ran / n, "exposed_seconds": exposed / n}


def idle_gaps(trace: dict, top: int = 10) -> List[list]:
    """The longest idle gaps of the busiest device inside the window,
    each named by the host span that covers most of it."""
    lo, hi = window_of(trace)
    busy = busy_by_device(trace)
    if not busy:
        return []
    device = max(busy, key=busy.get)
    covered = union(clip(trace["devices"][device], lo, hi))
    gaps = subtract([(lo, hi)], covered)
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    spans = [s for s in trace["spans"] if s["name"] != "bench/window"]
    out = []
    for s, e in gaps[:top]:
        best, best_cover = "no bench span", 0.0
        for sp in spans:
            cover = min(e, sp["end"]) - max(s, sp["start"])
            if cover > best_cover:
                best, best_cover = sp["name"], cover
        out.append([best, e - s])
    return out


def summary(trace: dict, window_s: float) -> dict:
    """`busy_s` (mean over the chips), `window_s` (the measured window by
    the host's clock, which the trace's `bench/window` span also spans)
    and the breakdown the result line carries."""
    busy = busy_by_device(trace)
    if not busy or max(busy.values()) <= 0:
        raise RuntimeError("the trace holds no device operation inside "
                           "the measured window")
    ops = sorted(class_seconds(trace).items(), key=lambda kv: -kv[1])
    return {"busy_s": sum(busy.values()) / len(busy),
            "busiest_s": max(busy.values()),
            "window_s": window_s,
            "breakdown": {"device_ops": [[k, v] for k, v in ops[:10]],
                          "idle_gaps": idle_gaps(trace)}}
