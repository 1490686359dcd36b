"""Unified observability layer: metrics + tracing + profiling hooks.

Three coordinated parts (docs/observability.md):

- :mod:`veles_tpu.observe.metrics` — the process-global
  :class:`MetricsRegistry` with Prometheus text exposition, mounted as
  ``/metrics`` on every HTTP surface via
  ``core/httpd.py:serve_metrics``; weak *bridges* re-publish the
  existing state holders (ServingHealth, ContinuousDecoder, Loader,
  the fleet master) at scrape time;
- :mod:`veles_tpu.observe.tracing` — trace_id/span_id spans through
  the EventRecorder, propagated by the ``X-Veles-Trace`` serving
  header and the fleet frames' ``trace`` field; exported to Chrome
  trace JSON by ``veles_tpu observe export-trace``;
- :mod:`veles_tpu.observe.profile` — ``--profile`` windows around
  a run with span-named ``jax.profiler.TraceAnnotation``s;
- :mod:`veles_tpu.observe.xla_stats` — device truth: XLA compile/cache
  counters with recompilation-storm detection, per-device memory
  gauges, online MFU from ``cost_analysis`` FLOPs;
- :mod:`veles_tpu.observe.reqledger` — request truth: the bounded
  lock-free per-request ledger (stage waterfalls + dispatch/KV/compile
  attribution) behind ``GET /debug/requests``, the ``veles_tpu observe
  slo`` autopsy CLI and the black-box request tails;
- :mod:`veles_tpu.observe.slo` — the SLO engine: configurable
  objectives over multi-window rolling buckets exported as
  ``veles_slo_*`` burn-rate gauges (per-tenant slices, fleet
  piggyback), plus the exemplar-linked request latency histograms;
- :mod:`veles_tpu.observe.governor` — the closed loop over all of the
  above: the serving governor reads burn rates, pool release windows
  and compile windows and ACTS — graceful tier degradation with
  hysteresis, admission resize + priced Retry-After, AOT prewarm,
  proactive breaker trips — every actuation ledger-visible
  (``veles_governor_*`` gauges, flight-ring entries, demotion marks on
  request rows);
- :mod:`veles_tpu.observe.flight` — the always-on bounded flight
  recorder that dumps a black-box JSON on breaker trips, epoch fences,
  unit exceptions and SIGTERM (``veles_tpu observe blackbox``);
- :mod:`veles_tpu.observe.history` — the metric flight recorder: a
  bounded lock-free time-series store sampling the full registry
  (counters as rates), a declarative anomaly rule engine
  (threshold/slope/drop-vs-baseline with seed rules), atomic incident
  artifacts naming the LEADING INDICATOR of a breach, the
  ``/debug/history`` surface, web-status sparklines, fleet piggyback
  and the ``veles_tpu observe incident`` CLI — the governor's
  burn/pressure sensing reads the same store the autopsies report;
- :mod:`veles_tpu.observe.servescope` — the serving goodput
  observatory: a bounded lock-free per-dispatch accounting ring fed by
  the slot engine (dense and paged) decomposing serving wall into
  prefill/decode/host/idle and dispatched tokens into useful vs
  waste-by-cause (bucket padding, duplicate rows, span/page overshoot,
  dead slots, discards), per-slot occupancy timelines behind
  ``GET /debug/serve`` and ``veles_tpu observe serve-trace``, and
  detector-owned waste/occupancy anomaly rules whose incidents name
  the dominant waste cause;
- :mod:`veles_tpu.observe.regress` — the artifact-proof bench sentinel:
  incremental atomic BENCH writes with SHA-256 sidecars, and the
  ``veles_tpu observe regress`` comparison gate (``make regress``);
- :mod:`veles_tpu.observe.replay` — production traffic record-replay
  (docs/traffic_replay.md): anonymized versioned JSONL traces exported
  from the request ledger (salted tenant hashes, loss-stamped headers,
  sha256 sidecars — ``veles_tpu observe record``) and the open-loop
  replayer with deterministic seeded time-warps (xN rate, tenant-mix
  reweighting, long-context skew, burst compression — ``observe
  replay``);
- :mod:`veles_tpu.observe.capacity` — the capacity-cliff finder
  (``veles_tpu observe capacity``): escalate a replayed trace's warp
  until an SLO objective breaches, back off and bisect the cliff, and
  emit a report artifact whose incident handoff names the
  first-breaching series and the dominant servescope waste cause — its
  keys (``capacity_sustained_tokens_per_sec`` etc.) are regress-gated.

Everything is off by default with a structurally no-op fast path: the
disabled tracer hands out one shared null span, the disabled registry
returns before its lock — hot paths pay one attribute check. The one
exception is the flight recorder, which is ON by default but records
only at the already-ms-scale dispatch/span sites with a bounded
lock-free append (the overhead guard covers it too).
"""

from veles_tpu.observe.flight import (  # noqa: F401
    FlightRecorder, get_flight_recorder)
from veles_tpu.observe.history import (  # noqa: F401
    AnomalyRule, IncidentRecorder, MetricHistory, default_rules,
    ensure_metric_history, get_metric_history, parse_history_spec,
    set_metric_history, sparkline, start_history_sampler,
    stop_history_sampler)
from veles_tpu.observe.metrics import (  # noqa: F401
    DEFAULT_BUCKETS, MetricsRegistry, bridge, get_metrics_registry,
    publish_decoder, publish_fleet, publish_loader,
    publish_serving_health)
from veles_tpu.observe.capacity import (  # noqa: F401
    CapacityFinder, render_capacity_report, write_capacity_report)
from veles_tpu.observe.replay import (  # noqa: F401
    hash_tenant, load_trace, plan_fingerprint, record_trace, replay,
    warp_plan, write_trace)
from veles_tpu.observe.reqledger import (  # noqa: F401
    RequestLedger, get_request_ledger, publish_request_ledger)
from veles_tpu.observe.servescope import (  # noqa: F401
    ServeScope, ensure_serve_registered, get_serve_scope,
    publish_serve_scope)
from veles_tpu.observe.slo import (  # noqa: F401
    SLOEngine, get_slo_engine, observe_request, parse_objectives)
from veles_tpu.observe.tracing import (  # noqa: F401
    NULL_SPAN, TRACE_HEADER, Tracer, current_context,
    format_trace_header, get_tracer, parse_trace_field,
    parse_trace_header)
from veles_tpu.observe.xla_stats import (  # noqa: F401
    CompileTracker, ensure_registered, get_compile_tracker,
    instrument, program_flops)
