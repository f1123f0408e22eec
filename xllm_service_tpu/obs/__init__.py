"""obs — the observability core: metrics registry + request spans.

Dependency-free (stdlib only), thread-safe, shared by both planes:

- ``metrics``: Counter / Gauge / Histogram families with label sets,
  log-spaced latency buckets, Prometheus text exposition. Every
  ``/metrics`` line in this repo renders through a ``Registry``
  (enforced by the ``metrics-registry`` xlint rule).
- ``expfmt``: the read side — exposition parsing, structural histogram
  validation (tier-1 tests), and ``histogram_quantile`` (latency
  percentiles out of rendered histogram text).
- ``spans``: per-request stage timelines in a bounded ring, merged
  across the service/worker boundary by correlation id and served at
  ``GET /admin/trace/<request_id>``.
- ``events``: the bounded structured cluster event log (closed
  taxonomy, ``event-catalog`` xlint rule) behind ``GET /admin/events``.
- ``failpoints``: deterministic fault injection — a closed catalog of
  named failure sites (``failpoint-catalog`` xlint rule), armed via
  ``XLLM_FAILPOINTS`` / ``POST /admin/failpoint``; the chaos tests'
  lever (docs/ROBUSTNESS.md).
- ``slo``: the judgment layer — multi-window SLO burn-rate engine and
  the watchdog's anomaly detector, behind ``GET /admin/slo`` and the
  ``xllm_slo_*`` / ``xllm_anomaly_active`` series.
- ``profiler``: the master watching itself — closed-catalog hot-path
  section timers (``hotpath-section-catalog`` xlint rule),
  lock-contention mirrors, per-thread-root CPU, self-gauges, and the
  ``GET /admin/profile`` stack sampler.

See docs/OBSERVABILITY.md for the full series and stage catalogue.
"""

from xllm_service_tpu.obs.events import (           # noqa: F401
    EVENT_TYPES, EventLog)
from xllm_service_tpu.obs.failpoints import (       # noqa: F401
    FAILPOINTS, Failpoints)
from xllm_service_tpu.obs.expfmt import (           # noqa: F401
    fraction_le_from_buckets, histogram_fraction_le, histogram_quantile,
    parse_exposition, validate_exposition)
from xllm_service_tpu.obs.metrics import (          # noqa: F401
    DEFAULT_LATENCY_BUCKETS_MS, Counter, Gauge, Histogram, Registry,
    default_registry)
from xllm_service_tpu.obs.profiler import (         # noqa: F401
    HOTPATH_BUCKETS_MS, SECTIONS)
from xllm_service_tpu.obs import profiler           # noqa: F401
from xllm_service_tpu.obs.slo import (              # noqa: F401
    AnomalyDetector, InstanceSignal, SloConfig, SloEngine, SloObjective)
from xllm_service_tpu.obs.spans import (            # noqa: F401
    FIRST_TOKEN_STAGES, FIRST_TOKEN_STAMPS, FRONT_MS_HEADER,
    REQUEST_ID_HEADER, SCHEDULE_MS_HEADER, SERVICE_STAGES, WORKER_STAGES,
    SpanStore, first_token_stages)
