"""Unified telemetry plane: metrics registry, step tracing, Prometheus
exposition, device gauges.

Before this package the reproduction had re-grown the reference's
observability fragmentation (SLF4J score lines + the Hazelcast
tracker's ad-hoc counters): `StepTimeListener` kept its own list,
`EngineStats` its own lock-and-dict, the guardian logged events, the
device feed counted buckets privately, and none of it shared a data
model or an export path. Now every hot path publishes into ONE
process-global `MetricsRegistry`:

- training: `dl4j_train_steps`, `dl4j_train_examples`,
  `dl4j_train_step_seconds{source=}`, `dl4j_train_loss`,
  `dl4j_train_epochs` (MultiLayerNetwork fit/fit_scan and the
  DP/ZeRO-1/TP trainers);
- guardian: `dl4j_guardian_events{kind=skip|rollback|abort|autosave|
  preempt}`;
- device feed: `dl4j_feed_batches`, `dl4j_feed_padded_examples`,
  `dl4j_feed_bucket_hits{bucket=}`, `dl4j_feed_prefetch_depth`;
- serving: `dl4j_serve_requests{engine=}`, rows/padded/errors,
  `dl4j_serve_latency_seconds`, `dl4j_serve_bucket_forwards`,
  `dl4j_batcher_*` + queue depth;
- device: `dl4j_device_memory_bytes{device=,stat=}`,
  `dl4j_jit_programs{cache=}` recompile counters;
- host process (`telemetry/host.py`, started by the first decode
  loop): `dl4j_host_gc_seconds{generation=}`,
  `dl4j_host_gc_collections{generation=}` (the collector's pauses) and
  `dl4j_host_lag_seconds_max` (an interpreter heartbeat's delay);
- checkpoint: `dl4j_ckpt_saves/bytes_written/errors`,
  `dl4j_ckpt_snapshot_seconds` (step-loop stall) /
  `dl4j_ckpt_write_seconds`, in-flight + last-committed-step gauges,
  `dl4j_serve_reloads` (docs/CHECKPOINTS.md).

Export: `GET /metrics` (Prometheus text) and `GET /snapshot` (JSON) on
the serving server, the scaleout StatusServer, or a standalone
`exposition.start_metrics_server()`. Tracing: `span("train_step")`
regions with Chrome-trace export, always-on phase totals for the
decode scheduler, and a `jax.profiler.TraceAnnotation` of the same name
around every span that runs (trace.py). Catalogue, scrape
quickstart and overhead envelope: docs/OBSERVABILITY.md.
"""

from deeplearning4j_tpu.telemetry.registry import (  # noqa: F401
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    enabled,
    get_registry,
    set_enabled,
)
from deeplearning4j_tpu.telemetry.trace import (  # noqa: F401
    PhaseTotals,
    SpanRecord,
    Tracer,
    active_tracer,
    chrome_trace,
    save_chrome_trace,
    span,
    start_tracing,
    stop_tracing,
)

__all__ = [
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "DEFAULT_BUCKETS",
    "get_registry", "set_enabled", "enabled",
    "counter", "gauge", "histogram",
    "span", "start_tracing", "stop_tracing", "active_tracer",
    "chrome_trace", "save_chrome_trace", "Tracer", "SpanRecord",
    "PhaseTotals",
]


def counter(name: str, help: str = ""):
    """Get-or-create a counter family on the global registry."""
    return get_registry().counter(name, help)


def gauge(name: str, help: str = ""):
    """Get-or-create a gauge family on the global registry."""
    return get_registry().gauge(name, help)


def histogram(name: str, help: str = "", **kw):
    """Get-or-create a histogram family on the global registry."""
    return get_registry().histogram(name, help, **kw)
