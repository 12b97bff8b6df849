"""Unified observability: metrics registry, span tracer, profile reports.

  * ``obs.metrics`` — labelled counters/gauges/timers/histograms; a
    per-query registry lives on ``ExecContext``, the process-wide
    ``REGISTRY`` serves subsystems that outlive a query.
  * ``obs.trace`` — structured spans with Chrome trace-event export
    (``spark.rapids.tpu.trace.path``, open in Perfetto).
  * ``obs.profile`` — per-query plan-tree profile reports
    (``session.profile_report()`` / ``session.profile_json()``).

See docs/observability.md for the span names and config keys.
"""

from spark_rapids_tpu.obs.metrics import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, REGISTRY, Timer,
    registry_delta,
)
from spark_rapids_tpu.obs.trace import TRACER, Tracer  # noqa: F401
from spark_rapids_tpu.obs.profile import (  # noqa: F401
    ProfileReport, build_profile,
)
