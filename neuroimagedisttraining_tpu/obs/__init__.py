"""Observability subsystem: tracing, metrics registry, per-round telemetry.

The third leg after ``parallel/`` (comm-efficient aggregation) and
``robust/`` (fault tolerance): the layer every perf PR is measured with.

* :mod:`~.trace` — hierarchical host-side span tracer emitting Chrome
  trace-event JSON (Perfetto-viewable), each span mirrored into
  ``jax.profiler.TraceAnnotation`` so host spans line up with the XLA
  device trace. Module-level null tracer = zero-cost when disabled.
  One tree per process (ids, parents, the round; its docstring lists
  the span names): compile durations (:mod:`~.compile`) land in it as
  children of the span that dispatched them, and the allocator's
  ``bytes_in_use`` / ``peak_bytes_in_use`` at the exit of every
  top-level span, beside :mod:`~.memory`'s gauges and the registry's
  compile distributions, which read as before.
* :mod:`~.metrics` — typed registry: counters, gauges, streaming
  distributions (count/sum/min/max/p50/p99), labeled children behind a
  bounded-cardinality guard.
* :mod:`~.export` — sinks: per-round JSONL stream, end-of-run
  ``metrics.json`` merged into ``save_stat_info``, optional TensorBoard
  scalars. Multihost-aware: every process records, only process 0
  exports; ``merge_host_jsonl`` folds per-host streams into one timeline.
* :mod:`~.memory` — device-HBM watermark + host-RSS sampling at round
  boundaries, surfaced as gauges.

The ANALYSIS half — from recording to diagnosis (offline, CLI:
``python -m neuroimagedisttraining_tpu.obs analyze <run_dir>``):

* :mod:`~.analyze` — per-phase round-time attribution, robust
  outlier/straggler rounds, memory-leak flagging, fault-recovery and
  compile-cost summaries; versioned ``analysis.json`` + human report.
* :mod:`~.health` — per-client/per-site ledger: participation and
  fault attribution via deterministic replay, per-site accuracy
  trajectories, degraded-site flags.
* :mod:`~.compile` — compile-time observability: per-entry-point
  compile wall-time via ``jax.monitoring`` listeners, cache-hit
  counters, AOT ``cost_analysis()`` FLOPs/bytes.

The NUMERICS half — what happens inside the jitted round:

* :mod:`~.numerics` — in-jit training-dynamics telemetry
  (``--obs_numerics``): per-layer-group update/grad norms, non-finite
  precursor gauges, per-client drift/cosine, SalientGrads mask
  churn/agreement — returned through the round outputs as f32 scalars,
  so fused blocks stay sync-free.
* :mod:`~.recorder` — anomaly flight recorder (``--flight_recorder``):
  bounded post-mortem bundles (trigger detail + last-K rounds of
  numerics JSONL + optional retry-round device trace) when the guard
  quarantines, the watchdog rolls back, or a drift trigger trips.

The COMMUNICATION half — where the aggregation's bytes and time go:

* :mod:`~.comm` — the analytical wire-cost model (``--obs_comm``):
  bytes-on-the-wire per ``agg_impl`` and per top-level leaf group at
  the live mask density, a once-per-run timed probe of the
  algorithm's own aggregation path, and ``Message`` serialized-size
  accounting — per-round ``comm_*`` JSONL stamps (obs schema v3).
* :mod:`~.devtrace` — ``jax.profiler`` device-trace parsing:
  collective-vs-compute time attribution (measured agg share,
  achieved wire GB/s vs the model), with a ``jit_cost_analysis``
  FLOPs/bytes fallback when no trace was captured.

The FLEET half — across runs, not within one:

* :mod:`~.catalog` — the append-only run catalog
  (``results/runs_index.jsonl``): one line per recorded run (identity,
  lineage keys, identity-bearing flags, git SHA, final metrics, end
  run-health, event counts, artifact paths), written at session close,
  rebuildable from run dirs for pre-catalog runs (``obs ls``).
* :mod:`~.diff` — the three-plane cross-run diff engine (``obs
  diff``): config plane (identity vs inert flag splits via the flag
  census), trajectory plane (round-aligned per-metric comparison with
  first-divergence round + MAD-band significance), event/health plane
  (event diffs keyed ``(round, type)``, health-trajectory diffs) —
  plus bit-exact param-tree diffs. ``--expect identical`` exit codes
  make it the one comparator every smoke twin check routes through.
* :mod:`~.report` — the byte-deterministic static HTML fleet report
  (``obs report``): per-run sparklines, health/event timelines, the
  wire-cost table, the rounds/sec-vs-cohort scatter.

The ONLINE half — in-run SLO evaluation while the run is live:

* :mod:`~.slo` — the online SLO engine (``--slo_spec``): a declarative
  objective DSL evaluated incrementally at the record hook with
  O(1)-memory streaming estimators (windowed/P² quantiles, windowed
  rates, EWMA, least-squares slope), SRE-style error budgets with
  fast/slow burn-rate alerts, and the ``OK -> DEGRADED -> FAILING``
  run-health state machine stamped on every JSONL line
  (``--slo_enforce`` turns a FAILING end state into a nonzero exit).
* :mod:`~.events` — the typed, severity-ranked event bus
  (``SLO_BREACH`` / ``BUDGET_BURN`` / ``GUARD`` / ``WATCHDOG`` /
  ``DRIFT`` / ``HEALTH_TRANSITION``) with pluggable sinks: the per-run
  ``<identity>.events.jsonl`` stream, the flight-recorder ``slo``
  trigger adapter, ``obs tail --events`` live rendering.

Nothing here enters run/checkpoint identity: telemetry never forks a
lineage, and with ``--obs`` off every hook is a no-op (bit-identical to
the pre-obs behavior — ``scripts/obs_smoke.py`` enforces it;
``scripts/slo_smoke.py`` adds the SLO-layer contract).
"""
from . import (
    analyze,
    catalog,
    comm,
    compile,
    devtrace,
    diff,
    events,
    export,
    health,
    memory,
    metrics,
    numerics,
    recorder,
    report,
    slo,
    trace,
)

__all__ = ["analyze", "catalog", "comm", "compile", "devtrace",
           "diff", "events", "export", "health", "memory", "metrics",
           "numerics", "recorder", "report", "slo", "trace"]
