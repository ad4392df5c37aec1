"""Set-up in the program's own record (ISSUE 36): what the process did
between its start and its first train step, and what JAX compiled or
read from its cache on the way.

Two kinds of record go to `memory_tracer()`, beside the input
pipeline's:

  `setup/*`    spans opened as context managers around the phases of a
               model's construction (`models/jax_model.py`,
               `models/model_base.py`, `models/vm_model.py`) and around
               the backend's first touch and the model's imports
               (`code2vec.py`: `setup/backend`, `setup/imports`). None
               waits for the device: a span that ends with device work
               in flight is the host's time.
  `compile/*`  one retroactive record for each interval JAX reports
               through `jax.monitoring`: `compile/trace` (the function
               to a jaxpr), `compile/lower` (the jaxpr to StableHLO),
               `compile/backend` (XLA's compile, or the read of the
               persistent cache in its place). `CompileRecorder` turns
               JAX's callbacks into the records; `install` registers
               one with `jax.monitoring`, which the caller hands in
               (`device.enable_compile_cache`): nothing here imports
               jax (tests/test_obs_guard.py). A listener runs only when
               JAX compiles, so a warm step runs none of this.

Every `compile/*` record carries `fun_name` (JAX's name for the
program, `jit(...)` stripped, so the three records of one program share
it; every eager `jnp` operation is a program of its own), `nth` (the
ordinal of that name's `compile/backend` records in this process: the
staircase step and the full step are both `step`), and `under` (the
name of the innermost span the compiling thread held open, or None).
`compile/backend` also carries `cache`: `"hit"` (read from the
persistent cache, with `retrieval_s`), `"miss"` (looked up, not found,
compiled) or `"off"` (the cache was not asked).

`summarize` reduces the records to where set-up went; `format_line`
is the one line `train()` logs after its first step, and
`export` sends the records through a run's `--trace` tracer, where
`tools/trace_report.py` prints them as its "Set-up" table by the same
`summarize`.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

from code2vec_tpu.obs.trace import Tracer, memory_tracer

__all__ = ["CompileRecorder", "export", "format_line", "install",
           "report", "summarize", "union_seconds"]

# JAX's time-span event -> the record's name
_SPAN_NAMES = {
    "/jax/core/compile/jaxpr_trace_duration": "compile/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile/lower",
    "/jax/core/compile/backend_compile_duration": "compile/backend",
}
# JAX's cache events, all inside the backend interval and on its
# thread. A request that asked the cache and names no hit afterwards
# compiled (`cache_misses` itself is only sent for an entry large and
# slow enough to be written back).
_CACHE_STATES = {
    "/jax/compilation_cache/compile_requests_use_cache": "miss",
    "/jax/compilation_cache/cache_misses": "miss",
    "/jax/compilation_cache/cache_hits": "hit",
}
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


def _program_name(fun_name: str) -> str:
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


class CompileRecorder:
    """`jax.monitoring`'s three kinds of listener over one tracer.
    JAX calls them on the thread that compiles; what a cache event
    says waits in a thread-local for the backend interval that closes
    around it."""

    def __init__(self, tracer: Tracer, wall=time.time):
        self._tracer = tracer
        self._wall = wall
        self._lock = threading.Lock()
        self._backend_count: Dict[str, int] = {}
        self._pending = threading.local()

    def on_event(self, event: str, **_kw) -> None:
        state = _CACHE_STATES.get(event)
        if state is not None:
            self._pending.cache = state

    def on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == _RETRIEVAL_EVENT:
            self._pending.retrieval_s = duration

    def on_time_span(self, event: str, start: float, end: float,
                     fun_name: str = "", **_kw) -> None:
        name = _SPAN_NAMES.get(event)
        if name is None:
            return
        fun_name = _program_name(fun_name)
        pending = vars(self._pending)       # this thread's
        if name == "compile/trace":
            # JAX reports every jit it traces, the hundreds nested in a
            # step's trace too, whose intervals lie inside the step's.
            # A program's own trace is the last of its name before its
            # lowering: it waits for that
            pending.setdefault("traces", {})[fun_name] = (start, end)
            return
        tracer = self._tracer
        # JAX's interval is on time.time(), the record on the tracer's
        # clock: one paired read of both carries it over
        shift = tracer.clock() - self._wall()
        backend = name == "compile/backend"
        with self._lock:
            nth = self._backend_count.get(fun_name, 0) + 1
            if backend:
                self._backend_count[fun_name] = nth
        held = tracer.current_span()
        attrs: Dict[str, Any] = {
            "fun_name": fun_name, "nth": nth,
            "under": held.name if held is not None else None}
        if backend:
            attrs["cache"] = pending.pop("cache", "off")
            retrieval_s = pending.pop("retrieval_s", None)
            if attrs["cache"] == "hit" and retrieval_s is not None:
                attrs["retrieval_s"] = retrieval_s
        else:
            traced = pending.get("traces", {}).pop(fun_name, None)
            if traced is not None and traced[1] <= start:
                tracer.record_span("compile/trace", traced[0] + shift,
                                   traced[1] + shift, **attrs)
        tracer.record_span(name, start + shift, end + shift, **attrs)


_INSTALL_LOCK = threading.Lock()
_INSTALLED: Optional[CompileRecorder] = None


def install(monitoring) -> CompileRecorder:
    """Register the process's one `CompileRecorder` (over
    `memory_tracer()`) with `monitoring`, the `jax.monitoring` module
    the caller imported; a second call registers nothing."""
    global _INSTALLED
    with _INSTALL_LOCK:
        if _INSTALLED is None:
            recorder = CompileRecorder(memory_tracer())
            monitoring.register_event_time_span_listener(
                recorder.on_time_span)
            monitoring.register_event_listener(recorder.on_event)
            monitoring.register_event_duration_secs_listener(
                recorder.on_duration)
            _INSTALLED = recorder
    return _INSTALLED


# ---- reading the record ------------------------------------------------

def union_seconds(intervals: Sequence[tuple]) -> float:
    """The length of the union of `(t0, t1)` intervals (a nested jit's
    trace lies inside its caller's; two threads may compile at once)."""
    total, end = 0.0, float("-inf")
    for t0, t1 in sorted(intervals):
        if t1 > end:
            total += t1 - max(t0, end)
            end = t1
    return total


def summarize(records: Sequence[Dict[str, Any]]
              ) -> Optional[Dict[str, Any]]:
    """Where set-up went, from recorder dicts (`name`, `t0`, `t1`,
    `attrs`) named `setup/*` and `compile/*`; None without a
    `setup/model` (the last one counts: a process may build several).

      model_s       `setup/model`'s duration
      phases        [(name, seconds, attrs)], longest first: the
                    `setup/*` spans inside it by their own time (two
                    of one name are one phase), and `(self)`, what of
                    `setup/model` no child covers
      outside       [(name, seconds, attrs)] in the order they began:
                    the `setup/*` spans before that model and outside
                    it (`code2vec.py`'s `backend` and `imports`)
      programs, from_cache, compiled
                    `compile/backend` records, those of them with
                    `cache == "hit"`, and the rest
      compile_s     the union of every `compile/*` interval
      longest       [(fun_name, seconds, programs)], longest first:
                    the union of each name's `compile/*` intervals
    """
    setup = [r for r in records if r["name"].startswith("setup/")]
    models = [r for r in setup if r["name"] == "setup/model"]
    if not models:
        return None
    model = models[-1]
    inside = [r for r in setup if r is not model
              and model["t0"] <= r["t0"] and r["t1"] <= model["t1"]]
    model_s = model["t1"] - model["t0"]
    by_phase: Dict[str, list] = {}      # name -> [seconds, attrs]
    for r in inside:                    # `setup/restore` comes twice
        phase = by_phase.setdefault(r["name"][len("setup/"):], [0.0, {}])
        phase[0] += r["t1"] - r["t0"]
        phase[1].update(r["attrs"])
    by_phase["(self)"] = [model_s - union_seconds(
        [(r["t0"], r["t1"]) for r in inside]), {}]
    phases = sorted(((name, s, attrs)
                     for name, (s, attrs) in by_phase.items()),
                    key=lambda p: -p[1])
    compiles = [r for r in records if r["name"].startswith("compile/")]
    programs = [r for r in compiles if r["name"] == "compile/backend"]
    hits = sum(r["attrs"].get("cache") == "hit" for r in programs)
    by_name: Dict[str, List[tuple]] = {}
    for r in compiles:
        by_name.setdefault(r["attrs"].get("fun_name", "?"), []).append(
            (r["t0"], r["t1"]))
    count = collections.Counter(r["attrs"].get("fun_name", "?")
                                for r in programs)
    longest = sorted(((name, union_seconds(spans), count[name])
                      for name, spans in by_name.items()),
                     key=lambda p: -p[1])
    # the newest of each name: a process may build several models
    outside = {r["name"]: r for r in setup
               if r["name"] != "setup/model" and r["t1"] <= model["t0"]
               and not any(m["t0"] <= r["t0"] and r["t1"] <= m["t1"]
                           for m in models)}
    return {"model_s": model_s, "phases": phases,
            "model_attrs": model["attrs"],
            "outside": [(r["name"][len("setup/"):], r["t1"] - r["t0"],
                         r["attrs"])
                        for r in sorted(outside.values(),
                                        key=lambda r: r["t0"])],
            "programs": len(programs), "from_cache": hits,
            "compiled": len(programs) - hits,
            "compile_s": union_seconds([(r["t0"], r["t1"])
                                        for r in compiles]),
            "longest": longest}


def format_line(summary: Dict[str, Any], top: int = 3) -> str:
    """The operator's line: the phases of `setup/model` by their own
    time, then the programs, then the `top` longest by name."""
    phases = ", ".join(f"{name} {s:.2f}"
                       for name, s, _attrs in summary["phases"])
    longest = ", ".join(
        f"{name} {s:.2f} s" + (f" ({n} programs)" if n > 1 else "")
        for name, s, n in summary["longest"][:top])
    outside = "".join(f", {name} {s:.2f} s"
                      for name, s, _attrs in summary["outside"])
    return (
        f"set-up: model {summary['model_s']:.2f} s ({phases}){outside}"
        f"; {summary['programs']} programs, {summary['from_cache']} "
        f"from the cache, {summary['compiled']} compiled, "
        f"{summary['compile_s']:.2f} s"
        + (f"; longest: {longest}" if longest else ""))


def export(tracer: Tracer, records: Sequence[Dict[str, Any]]) -> int:
    """The `setup/*` and `compile/*` records through a run's `--trace`
    tracer, as `infeed/produce` goes (both clocks are
    `time.monotonic`): set-up's spans on the calling thread's row, the
    children under `setup/model`'s trace; JAX's intervals on a virtual
    `compile` row. Returns how many went out; a disabled tracer takes
    none."""
    if not tracer.enabled:
        return 0
    model_ctx, model = None, None
    setup = [r for r in records if r["name"].startswith("setup/")]
    # a parent ends after its children: hand it out first
    for r in sorted(setup, key=lambda r: (r["t0"], -r["t1"])):
        inside = (model is not None and model["t0"] <= r["t0"]
                  and r["t1"] <= model["t1"])
        ctx = tracer.record_span(r["name"], r["t0"], r["t1"],
                                 parent=model_ctx if inside else None,
                                 **r["attrs"])
        if r["name"] == "setup/model":
            model_ctx, model = ctx, r
    compiles = [r for r in records if r["name"].startswith("compile/")]
    for r in compiles:
        tracer.record_span(r["name"], r["t0"], r["t1"], track="compile",
                           **r["attrs"])
    return len(setup) + len(compiles)


def report(log, tracer: Tracer) -> None:
    """What both train loops call once their first step is dispatched
    (its compile is in the record by then): the operator's line through
    `log`, and the records through the run's tracer."""
    recorder = memory_tracer()
    records = recorder.records("setup/") + recorder.records("compile/")
    summary = summarize(records)
    if summary is not None:
        log(format_line(summary))
    export(tracer, records)
