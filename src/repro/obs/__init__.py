"""repro.obs — observability spine: telemetry, metrics, tracing.

telemetry.py  jit-safe integer-only in-graph reductions computed
              alongside ``les.train_step(telemetry=True)``: per-layer
              bit-occupancy histograms, saturation counts, NITRO-ReLU
              dead units, optimiser-scalar evolution — bitwise-neutral
              to the training trajectory (test-enforced)
metrics.py    thread-safe MetricRegistry (counters/gauges/histograms,
              Prometheus-text + JSONL exposition, HTTP scrape server)
              — the spine ``serving.stats.EngineStats`` is built on
trace.py      monotonic-clock span tracer with thread-local nesting,
              JSONL export, optional jax.profiler bridge — wrapped
              around train-step phases and the FleetEngine batch
              lifecycle
layers.py     the jax.named_scope names of the LES train step's layers
              (block{i}/forward|local_loss|backward, output, update,
              dp/reduce_gradients), which every compiled instruction
              carries into a device profile
health.py     training-health rule engine over the telemetry records:
              saturation trends, int32 headroom early warning, dead-unit
              growth, optimiser-scalar stall — windowed, hysteretic,
              edge-triggered alerts fanned out to sinks and
              ``obs_alerts_total`` counters; online in launch/train.py
              or offline over any metrics.jsonl (``scan_jsonl``)

Metric catalogue, alert-rule catalogue and how-to: docs/OBSERVABILITY.md.
"""

from repro.obs.health import (
    SEVERITIES,
    Alert,
    DeadUnitGrowthRule,
    DpCompressFitRule,
    HeadroomRule,
    HealthMonitor,
    OptimizerStallRule,
    Rule,
    SaturationTrendRule,
    default_rules,
    jsonl_sink,
    print_sink,
    scan_jsonl,
)
from repro.obs.metrics import (
    REPRO_VERSION,
    MetricError,
    MetricRegistry,
    MetricsServer,
    latency_summary_ms,
    percentile,
    register_build_info,
    start_metrics_server,
)
from repro.obs.trace import NULL_TRACER, Span, Tracer

__all__ = [
    "Alert",
    "DeadUnitGrowthRule",
    "DpCompressFitRule",
    "HeadroomRule",
    "HealthMonitor",
    "MetricError",
    "MetricRegistry",
    "MetricsServer",
    "NULL_TRACER",
    "OptimizerStallRule",
    "REPRO_VERSION",
    "Rule",
    "SEVERITIES",
    "SaturationTrendRule",
    "Span",
    "Tracer",
    "default_rules",
    "jsonl_sink",
    "latency_summary_ms",
    "percentile",
    "print_sink",
    "register_build_info",
    "scan_jsonl",
    "start_metrics_server",
]
