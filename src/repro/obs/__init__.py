"""repro.obs — zero-dependency observability: metrics, tracing, logging.

The measurement substrate for the whole stack (stdlib only):

  metrics   — process-global thread-safe registry of counters / gauges /
              fixed-bucket histograms (p50/p90/p99 from bucket counts,
              label support, Prometheus text + serde-stamped JSON export)
  tracing   — nestable ``span()`` wall-time timers, the JIT-aware
              ``jit_span()`` (first-call compile vs steady-state execute),
              ``TimedRLock`` (lock wait and hold histograms), each also
              annotated on the profiler's clock (``annotate()``)
  logsetup  — structured one-line ``key=value`` stdlib logging,
              ``REPRO_LOG_LEVEL``-controlled

Consumers: ``repro.service`` (``GET /v1/metrics``, per-endpoint latency,
staleness gauges), ``repro.dynamics`` (event counters, incremental-vs-
rebuild maintenance timing), the jit'd core entry points
(``batcheval.diameters``, ``rollout.rollout_episodes``), and
``benchmarks/common.py`` (the same histogram implementation computes
BENCH JSON percentiles).  ``benchmarks/fig18_obs.py`` gates the whole
layer's overhead at <= 5% of the uninstrumented path.
"""
from .logsetup import configure, get_logger, kv  # noqa: F401
from .metrics import (REGISTRY, Counter, Gauge, Histogram,  # noqa: F401
                      MetricsRegistry, parse_prometheus)
from .tracing import (TimedRLock, annotate, current_span,  # noqa: F401
                      jit_phase, jit_span, reset_jit_state, span)

# Pre-registered hierarchical-overlay defaults: present (at zero) in every
# scrape even before ``repro.hier`` is imported, so dashboards and the
# service smoke test can pin panels/assertions on them unconditionally.
# ``repro.hier`` records into these same instruments (idempotent specs).
HIER_CLUSTERS = REGISTRY.gauge(
    "repro_hier_clusters",
    "cluster count of the currently served hierarchical overlay")
HIER_HEADRING_DIAMETER = REGISTRY.gauge(
    "repro_hier_headring_diameter",
    "diameter (ms) of the hierarchical overlay's head ring")
HIER_ROUTE_HOPS = REGISTRY.histogram(
    "repro_hier_route_hops",
    "per-level hop count of delivered hierarchical routes",
    labels=("level",),
    buckets=(1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64, 96, 128))

__all__ = [
    "REGISTRY", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "parse_prometheus", "span", "current_span", "jit_span", "jit_phase",
    "reset_jit_state", "TimedRLock", "annotate", "configure", "get_logger",
    "kv",
    "HIER_CLUSTERS", "HIER_HEADRING_DIAMETER", "HIER_ROUTE_HOPS",
]
