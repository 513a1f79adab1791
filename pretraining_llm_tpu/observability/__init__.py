"""Run-wide observability: events, spans, goodput, device/compile telemetry.

The resilience subsystem (resilience/) made multi-day runs *survive* faults;
this package makes the cost of surviving them visible. The flat metrics JSONL
records loss and windowed MFU, but restarts, rollbacks, eval, checkpoint
saves and recompiles are invisible in it — a 43.8%-MFU run and a run that
spent 20% of wall-clock replaying a poison window look identical. The pieces:

  - events.py  — structured, monotonic-timestamped run events (an EventBus
                 with in-process subscribers and an optional JSONL sink);
                 everything else in this package is a fold over the stream.
  - spans.py   — nested host-side spans. Each is a ``jax.profiler
                 .TraceAnnotation``, so a profiler trace holds it on the
                 device ops' own clock; a ``SpanRecorder`` keeps them in
                 memory too and exports Chrome trace-event JSON, once
                 somebody asked for one (``get_recorder``/``set_recorder``):
                 recording is opt-in, and costs an append to a list.
  - witness.py — the process's late-wake witness: a sleeper thread and a
                 sleeper child process whose late wakes say whether a slow
                 tick's thread could run, and if not whether the machine or
                 this interpreter held it; always on, started by the engine
                 and the train step.
  - goodput.py — folds the event stream into a wall-clock decomposition
                 (productive / replay / eval / checkpoint / restore / idle /
                 other) and a single ``goodput`` fraction. Replay detection
                 is a step high-water mark: re-run steps after a rollback
                 are never productive time.
  - device.py  — per-device HBM sampling (``Device.memory_stats()``) and a
                 jax.monitoring compile listener that turns post-warmup
                 backend compiles into ``recompile`` events, so a recompile
                 storm shows up in the stream instead of only as lost MFU.
  - export.py  — Prometheus textfile exporter (no server dependency): one
                 atomic write per log boundary for a node-exporter-style
                 scrape.
  - capacity.py — serving capacity accounting: per-window occupancy
                 samples (rows/tokens/pool/queue at the reap sync point)
                 and a typed scheduler decision log (reject/shed/preempt/
                 evict/reclaim), both ring-buffered and bus-emitted;
                 scripts/obs_report.py --capacity folds them into a
                 slot-second waterfall naming the binding constraint.

scripts/obs_report.py is the offline half: metrics/events JSONL in, goodput
breakdown + step-time histogram + event timeline out (run in CI over the
smoke run, making the JSONL schema a checked contract).

Everything here is host-side; recording between log boundaries performs no
device→host syncs (tested). The hub below is what the trainer wires in.
"""

from pretraining_llm_tpu.observability.capacity import (
    DECISION_KINDS,
    CapacitySampler,
    DecisionLog,
)
from pretraining_llm_tpu.observability.events import EVENT_KINDS, EventBus, sanitize_record
from pretraining_llm_tpu.observability.goodput import CATEGORIES, GoodputAccountant
from pretraining_llm_tpu.observability.spans import SpanRecorder, get_recorder, span
from pretraining_llm_tpu.observability.export import (
    lint_exposition,
    prometheus_lines,
    write_textfile,
)
from pretraining_llm_tpu.observability.metrics import (
    DEFAULT_LATENCY_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    log_buckets,
)
from pretraining_llm_tpu.observability.tracing import (
    RequestTrace,
    SpanContext,
    Tracer,
    format_traceparent,
    parse_traceparent,
)
from pretraining_llm_tpu.observability.device import CompileWatcher, DeviceTelemetry
from pretraining_llm_tpu.observability.hub import ObservabilityHub

__all__ = [
    "DECISION_KINDS",
    "CapacitySampler",
    "DecisionLog",
    "EVENT_KINDS",
    "EventBus",
    "sanitize_record",
    "CATEGORIES",
    "GoodputAccountant",
    "SpanRecorder",
    "get_recorder",
    "span",
    "lint_exposition",
    "prometheus_lines",
    "write_textfile",
    "DEFAULT_LATENCY_BUCKETS",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "log_buckets",
    "RequestTrace",
    "SpanContext",
    "Tracer",
    "format_traceparent",
    "parse_traceparent",
    "CompileWatcher",
    "DeviceTelemetry",
    "ObservabilityHub",
]
