"""code2vec_tpu.obs — unified run telemetry (ISSUE 2).

One registry (`Telemetry`: counters, gauges, p50/p95/p99 timer
histograms), pluggable sinks (per-run JSONL event log + manifest under
`--telemetry_dir`, TensorBoard adapter over `ScalarWriter`, stdout),
host-vs-device-explicit span helpers, and the train-loop recorder both
model heads share. Stdlib-only at import time — jax is lazy, TensorFlow
is never imported here (guard: tests/test_obs_guard.py).
"""

from code2vec_tpu.obs.alerts import (AlertEngine, AlertError,  # noqa: F401
                                     AlertRule, load_rules)
from code2vec_tpu.obs.exposition import (LivePlane,  # noqa: F401
                                         MetricsServer,
                                         build_live_plane,
                                         render_prometheus)
from code2vec_tpu.obs.fleet import (FleetCollector,  # noqa: F401
                                    fleet_alert_rules)
from code2vec_tpu.obs.health import HealthEngine  # noqa: F401
from code2vec_tpu.obs.loop import (TrainStepRecorder,  # noqa: F401
                                   infeed_produce_instrument)
from code2vec_tpu.obs import setup_trace  # noqa: F401
from code2vec_tpu.obs.sinks import (JsonlSink, ScalarSink,  # noqa: F401
                                    StdoutSink)
from code2vec_tpu.obs.telemetry import (SUMMARY_PERCENTILES,  # noqa: F401
                                        Telemetry, TimerStat,
                                        device_sync,
                                        format_latency_line)
from code2vec_tpu.obs.trace import (MemoryTracer,  # noqa: F401
                                    SpanChannel, SpanContext, Tracer,
                                    TraceSpan, memory_tracer)
from code2vec_tpu.obs.watchdog import (Heartbeat, StallError,  # noqa: F401
                                       Watchdog)
