"""Observability: tracing, metrics registry, structured logs, profiling.

The reference runtime's visibility story was scattered slf4j logging
plus a dropwizard servlet; SURVEY §5 prescribes a first-class
observability layer for the TPU build. This package is that layer, and
it is deliberately self-contained (stdlib + numpy only) so every other
subsystem — the serving engine, the scheduler, the KV pool, the
training orchestrator — can depend on it without cycles:

- :class:`~deeplearning4j_tpu.obs.trace.Tracer` — per-request span
  recording (Dapper-style) into a bounded ring buffer, exportable as
  Chrome-trace/Perfetto JSON. Zero-cost when disabled: every record
  call is a single attribute check.
- :class:`~deeplearning4j_tpu.obs.registry.MetricsRegistry` — typed
  counters / gauges / bounded histograms with a Prometheus
  text-format exporter (``/metrics`` on the serving server).
- :class:`~deeplearning4j_tpu.obs.registry.Reservoir` — fixed-size
  uniform sample (Algorithm R) with exact n/total/min/max, bounding
  long-run latency series without losing the percentile story.
- :mod:`~deeplearning4j_tpu.obs.logs` — structured JSON logging with
  request-id correlation across engine, scheduler and server.
- :class:`~deeplearning4j_tpu.obs.profiler.ProfileTrigger` — arms
  ``jax.profiler`` tracing around the next N engine steps
  (``POST /profile?s=N`` on the serving server, or a CLI flag).
- :mod:`~deeplearning4j_tpu.obs.compile_log` — what jax compiles in
  this process, by function and stage (``compile_log.install()``).
"""

from deeplearning4j_tpu.obs import compile_log  # noqa: F401

from deeplearning4j_tpu.obs.collect import (  # noqa: F401
    merge_trace_files,
    merge_traces,
)
from deeplearning4j_tpu.obs.flight import FlightRecorder, redact  # noqa: F401
from deeplearning4j_tpu.obs.logs import (  # noqa: F401
    JsonLogFormatter,
    configure_json_logging,
)
from deeplearning4j_tpu.obs.profiler import ProfileTrigger  # noqa: F401
from deeplearning4j_tpu.obs.registry import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Reservoir,
)
from deeplearning4j_tpu.obs.trace import (  # noqa: F401
    PhaseRegions,
    Tracer,
    format_traceparent,
    new_span_id,
    new_trace_id,
    parse_traceparent,
)
