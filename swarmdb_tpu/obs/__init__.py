"""Observability: request-span tracing + engine flight recorder.

Stdlib-only (no jax import) so the broker/runtime layers can record
spans in processes that never touch a device, and so swarmlint's CI job
can import the package without the ML stack.

- :mod:`.tracer` — per-thread ring-buffer span tracer, Chrome
  trace-event export (``GET /admin/trace/export``, bounded).
- :mod:`.flight` — fixed-size rings of engine-step and request records,
  dumped on watchdog restart and via ``GET /admin/flight``.
- :mod:`.propagate` — cluster-wide trace context (carried on the data
  plane / cluster-client / replication wires) and the per-node trace
  merge behind ``GET /admin/cluster/trace``.
- :mod:`.metrics` — lock-free fixed-bucket latency histograms exported
  in Prometheus histogram format from ``/metrics``, with per-bucket
  trace-id exemplars in OpenMetrics syntax.
- :mod:`.analyze` — offline trace/flight analyzer
  (``python -m swarmdb_tpu.obs.analyze``): per-completion cost
  decomposition and A/B regression attribution.
- :mod:`.sentinel` — the ONLINE counterpart (``GET /admin/slo``):
  rolling-window SLO monitor that learns a baseline, runs the analyzer's
  attributor in-process on breach, and auto-dumps flight + trace
  evidence tagged with the alert id.
- :mod:`.profiler` — swarmprof (``GET /admin/profile``): always-on
  device-time profiler — XLA cost-model harvest at warmup, per-variant
  invocation/device-time accounting, MFU/roofline classification,
  per-lane duty cycles, and the dispatch-shape (wave kind x width)
  profile.
- :mod:`.memprof` — swarmmem (``GET /admin/mem``): always-on KV/prefix
  memory accountant — pool occupancy decomposition + residency ages,
  the per-conversation hot/warm/cold temperature ledger, SHARDS-sampled
  miss-ratio curves over prefix-cache accesses, and the warm-tier /
  cold-resume what-if models ROADMAP item 3 is sized against.
- :mod:`.procwatch` — the process's watcher thread: late wakes, the
  kernel's scheduler accounts and every thread's frames at the wake become
  ``process.sample`` / ``process.stall`` / ``process.engine_late`` spans
  and ``process_*`` counters, so a process-wide stall names its cause.
"""

from . import procwatch, propagate
from .flight import FlightRecorder
from .memprof import MemProfiler, memprof, memprof_enabled
from .metrics import HISTOGRAMS, Histogram, HistogramRegistry
from .profiler import KernelProfiler, profile_enabled, profiler
from .sentinel import SLOConfig, SLOSentinel
from .tracer import TRACER, SpanTracer

__all__ = ["FlightRecorder", "SpanTracer", "TRACER", "procwatch",
           "propagate",
           "HISTOGRAMS", "Histogram", "HistogramRegistry",
           "SLOConfig", "SLOSentinel",
           "KernelProfiler", "profile_enabled", "profiler",
           "MemProfiler", "memprof", "memprof_enabled"]
