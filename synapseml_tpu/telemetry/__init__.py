"""Unified telemetry: metrics registry, span tracing, exposition, and
atomic JSON artifacts.

The observability spine of the TPU-native stack — the analogue (and
superset) of the reference's ``SynapseMLLogging`` structured verb
telemetry plus ``LightGBMPerformance.scala`` phase measures:

- :mod:`.registry` — process-wide ``Counter``/``Gauge``/``Histogram``
  with label sets; thread-safe, resettable (``get_registry()``).
- :mod:`.tracing` — nested host-side spans on the monotonic clock that
  also lie in any profiler capture, with Chrome-trace export
  (``span(name, **attrs)``, ``step_span(name)``, ``get_tracer()``).
- :mod:`.exposition` — Prometheus text + JSON rendering; served by
  ``ServingServer`` at ``GET /metrics``.
- :mod:`.artifact` — atomic, round-trip-verified JSON artifact writes
  (``write_json``): the supervisor's bundles, the flight recorder's
  dumps and the tuning table are written through it, so a reader never
  sees a truncated file.
- :mod:`.flight` — the crash flight recorder: a bounded,
  allocation-stable ring of structured events (collectives, checkpoint
  publishes, backoffs, fault firings, heartbeats, rowguard verdicts),
  dumped SIGKILL-atomically for post-mortem bundles.
- :mod:`.roofline` — the roofline auditor: XLA-captured bytes/flops +
  top byte-moving HLOs for any jitted step, and the chip peak tables
  behind the ``StepProfiler`` gauges.
- :mod:`.gangplane` — the gang-wide observability plane: cross-rank
  metric/span export over the ``SMLMP_TM:`` wire, ``worker_*{rank=}``
  mirroring into the coordinator's ``/metrics``, multi-lane Chrome-trace
  stitching, schema-checked ``postmortem.json`` bundles, and the
  :class:`~synapseml_tpu.telemetry.gangplane.StepProfiler` train-step
  decomposition (data/compute/collective).

Everything here is stdlib-only and safe to import before jax.

Instrumented layers (all write into the default registry):

====================================  =====================================
``parallel.collectives``              ``collective_calls_total`` /
                                      ``collective_bytes_total`` per op+axis
                                      (trace-time for jitted code),
                                      ``collective_latency_seconds`` for the
                                      host-dispatched allreduce
``models.gbdt`` (booster/trainer)     ``gbdt_phase_seconds`` per phase,
                                      ``gbdt_two_level_active`` gauge,
                                      ``gbdt_iterations_total``
``models.dl.training``                ``dl_train_samples_total`` /
                                      ``dl_train_tokens_total`` counters,
                                      ``dl_train_samples_per_sec`` gauge
``serving`` (server/continuous)       ``serving_records_total``,
                                      ``serving_records_per_sec``,
                                      ``serving_batch_size``,
                                      ``serving_errors_total`` (kinds now
                                      include ``parse`` and ``oom``),
                                      client-side continuous-mode counters
``resilience.rowguard``               ``rowguard_stage_calls_total``,
                                      ``rowguard_rows_total`` per outcome,
                                      ``rowguard_bisection_probes_total``,
                                      ``rowguard_oom_events_total``,
                                      ``rowguard_safe_batch_size`` gauge,
                                      ``quarantine_batches_total`` /
                                      ``quarantine_rows_total``,
                                      ``dataset_all_nan_columns_total``
====================================  =====================================
"""

from .artifact import (SchemaError, check_schema, dumps_checked, read_json,
                       write_json)
from .autotune import (AUTOTUNE_METRICS, Autotuner, CollectiveCostModel,
                       TuneSpace, fit_alpha_beta, register_space,
                       registered_spaces, resolve_entry_point)
from .exposition import (PROMETHEUS_CONTENT_TYPE, render_json,
                         render_prometheus)
from .flight import FlightRecorder, get_flight
from .gangplane import (GangPlane, StepProfiler, TM_MARKER,
                        check_postmortem, parse_telemetry, write_postmortem)
from .registry import (DEFAULT_BUCKETS, SERVING_TOKEN_LATENCY_BUCKETS,
                       SERVING_TTFT_BUCKETS, Counter, Gauge, Histogram,
                       MetricsRegistry, bucket_quantile, get_registry)
from .slo import (SLO_METRICS, SLOZ_SCHEMA, SLOZ_SCHEMA_VERSION, SloStore,
                  SloWindow, WindowedCounter, WindowedHistogram, check_sloz,
                  get_slo_store, plane_tenant, tenant_plane_name)
from .tracing import (RequestTraceStore, Span, Tracer, get_request_tracer,
                      get_tracer, mint_trace_id, span, step_span)
from .tunetable import (TUNE_TABLE_ENV, TUNE_TABLE_SCHEMA_VERSION, TunePlane,
                        check_tune_table, check_tunez, device_kind,
                        geometry_key, get_tuneplane, set_tuneplane)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "get_registry",
    "DEFAULT_BUCKETS", "SERVING_TTFT_BUCKETS",
    "SERVING_TOKEN_LATENCY_BUCKETS", "bucket_quantile",
    "Span", "Tracer", "get_tracer", "span", "step_span",
    "RequestTraceStore", "get_request_tracer", "mint_trace_id",
    "SloStore", "SloWindow", "WindowedCounter", "WindowedHistogram",
    "check_sloz", "get_slo_store", "SLOZ_SCHEMA", "SLOZ_SCHEMA_VERSION",
    "SLO_METRICS", "plane_tenant", "tenant_plane_name",
    "render_prometheus", "render_json", "PROMETHEUS_CONTENT_TYPE",
    "SchemaError", "check_schema", "dumps_checked", "write_json",
    "read_json",
    "FlightRecorder", "get_flight",
    "GangPlane", "StepProfiler", "TM_MARKER", "check_postmortem",
    "parse_telemetry", "write_postmortem",
    "AUTOTUNE_METRICS", "Autotuner", "CollectiveCostModel", "TuneSpace",
    "fit_alpha_beta", "register_space", "registered_spaces",
    "resolve_entry_point",
    "TUNE_TABLE_ENV", "TUNE_TABLE_SCHEMA_VERSION", "TunePlane",
    "check_tune_table", "check_tunez", "device_kind", "geometry_key",
    "get_tuneplane", "set_tuneplane",
]
