"""Where set-up went, from the program's own record: the `setup/*` spans
around the model's construction and one `compile/trace`, `/lower` and
`/backend` record for every program JAX traced, lowered and compiled or
read from its cache (`code2vec_tpu/obs/setup_trace.py` says what starts
and ends each; all in `code2vec_tpu.obs.trace.memory_tracer()`, on
`time.monotonic`).

The window opens at `t0` of the first of its pops: the last
`ctx.window["steps"]` `infeed/pop_wait` records that name a `seq`, walked
back over end-of-epoch markers, as `program_span.reduce` takes them. It
closes `ctx.window["seconds"]` later. Set-up is what ends before it
opens; the reference's compiles come after it has closed and are counted
nowhere.

`args["value"]`:
  model_s        the last `setup/model` span's duration
  compile_s      the union of the `compile/*` intervals that end before
                 the window opens (tracing, lowering, compiling, reading
                 the cache), in seconds; with `args["fun_name"]`, of that
                 program's alone
  programs       `compile/backend` records that end before the window
                 opens: executables built or fetched, the one-operation
                 programs of eager `jnp` calls among them
  cache_misses   those of them whose `cache` is not `"hit"`: 0 on a warm
                 run
  recompiles     `compile/backend` records that start inside the window

A program that keeps no `setup/` record gives None, as does a record
that no longer holds the window's pops. Says on standard error what the
split is: the phases of `setup/model` by their own time, the compiles
inside and outside it, the ten longest programs by name, the named
program's three intervals, and the programs (the ten longest) that missed
the cache or were compiled inside the window.
"""

import collections
import sys


def union_seconds(intervals) -> float:
    total, end = 0.0, float("-inf")
    for t0, t1 in sorted(intervals):
        if t1 > end:
            total += t1 - max(t0, end)
            end = t1
    return total


def window_open(records: list, steps: int):
    """`t0` of the first of the window's pops, or None when the record
    does not hold `steps` pops that name a batch."""
    pops = [r for r in records if r["name"] == "infeed/pop_wait"]
    named = [i for i, r in enumerate(pops) if "seq" in r["attrs"]]
    if not steps or len(named) < steps:
        return None
    first = named[-steps]
    while first > 0 and "seq" not in pops[first - 1]["attrs"]:
        first -= 1
    return pops[first]["t0"]


def _span(r) -> tuple:
    return r["t0"], r["t1"]


def reduce(records: list, steps: int, seconds: float):
    """The six values' parts from the recorder's span records (dicts
    with `name`, `t0`, `t1`, `attrs`), or None."""
    opened = window_open(records, steps)
    models = [r for r in records if r["name"] == "setup/model"
              and opened is not None and r["t1"] <= opened]
    if not models:
        return None
    model = models[-1]
    compiles = [r for r in records if r["name"].startswith("compile/")]
    before = [r for r in compiles if r["t1"] <= opened]
    programs = [r for r in before if r["name"] == "compile/backend"]
    inside = [r for r in records if r["name"].startswith("setup/")
              and r is not model
              and model["t0"] <= r["t0"] and r["t1"] <= model["t1"]]
    phases = {}
    for r in inside:
        name = r["name"][len("setup/"):]
        phases[name] = phases.get(name, 0.0) + r["t1"] - r["t0"]
    model_s = model["t1"] - model["t0"]
    phases["(self)"] = model_s - union_seconds(map(_span, inside))
    by_name = {}
    for r in before:
        by_name.setdefault(r["attrs"]["fun_name"], []).append(_span(r))
    return {
        "model_s": model_s, "phases": phases,
        "before": before, "programs": programs,
        "cache_misses": [r for r in programs
                         if r["attrs"].get("cache") != "hit"],
        "recompiles": [r for r in compiles
                       if r["name"] == "compile/backend"
                       and opened <= r["t0"] < opened + seconds],
        "compile_in_model_s": union_seconds(
            _span(r) for r in before
            if model["t0"] <= r["t0"] and r["t1"] <= model["t1"]),
        "by_name": {name: union_seconds(spans)
                    for name, spans in by_name.items()}}


def _say(text: str) -> None:
    print("setup_span: " + text, file=sys.stderr)


def read(ctx, args):
    try:
        from code2vec_tpu.obs.trace import memory_tracer
    except ImportError:     # a program that keeps no record
        return None
    rec = memory_tracer()
    got = reduce(rec.records("setup/") + rec.records("compile/")
                 + rec.records("infeed/pop_wait"),
                 ctx.window["steps"], ctx.window["seconds"])
    if got is None:
        return None
    value = args["value"]
    if value == "model_s":
        _say(f"setup/model {got['model_s']:.3f} s: " + ", ".join(
            f"{name} {s:.3f}" for name, s in
            sorted(got["phases"].items(), key=lambda p: -p[1])))
        return got["model_s"]
    if value == "compile_s":
        name = args.get("fun_name")
        if name is not None:
            mine = [r for r in got["before"]
                    if r["attrs"]["fun_name"] == name]
            for backend in (r for r in mine
                            if r["name"] == "compile/backend"):
                a = backend["attrs"]
                parts = {r["name"][len("compile/"):]: r["t1"] - r["t0"]
                         for r in mine if r["attrs"]["nth"] == a["nth"]}
                _say(f"{name} nth {a['nth']}: " + ", ".join(
                    f"{kind} {s:.3f} s" for kind, s in parts.items())
                    + f" (cache {a.get('cache')}"
                    + (f", retrieval {a['retrieval_s']:.3f} s)"
                       if "retrieval_s" in a else ")"))
            return got["by_name"].get(name, 0.0)
        total = union_seconds(map(_span, got["before"]))
        _say(f"compile/* before the window {total:.3f} s, "
             f"{got['compile_in_model_s']:.3f} s of it inside "
             "setup/model")
        return total
    if value == "programs":
        longest = sorted(got["by_name"].items(), key=lambda p: -p[1])[:10]
        count = collections.Counter(r["attrs"]["fun_name"]
                                    for r in got["programs"])
        _say("longest programs: " + ", ".join(
            f"{name} {s:.3f} s x{count[name]}" for name, s in longest))
        return len(got["programs"])
    if value in ("cache_misses", "recompiles"):
        found = got[value]
        if found:       # the ten longest: a cold run misses every one
            _say(f"{value}: " + ", ".join(
                "{fun_name} nth {nth} cache {cache} under {under} ".format(
                    **r["attrs"]) + f"{r['t1'] - r['t0']:.3f} s"
                for r in sorted(found, key=lambda r: r["t0"] - r["t1"])[:10]))
        return len(found)
    raise ValueError(f"setup_span: no value {value!r}")
