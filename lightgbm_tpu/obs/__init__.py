"""Run telemetry: event timeline, metrics registry, health monitors.

Every training run can self-instrument (the per-phase breakdowns that
"GPU-acceleration for Large-scale Tree Boosting" and "XGBoost: Scalable
GPU Accelerated Learning" ground their claims in, built into the loop):

* ``events``  — versioned JSONL event emitter (run header with params /
  backend / device topology, per-iteration phase records, compile events,
  memory snapshots, health verdicts, metrics snapshots) plus the
  ``RunObserver`` facade the training loop drives and the
  allocation-free ``NULL_OBSERVER`` it holds by default;
* ``timers``  — phase clocks and per-entry-point timers that fence with
  ``jax.block_until_ready`` for device-accurate timings and split the
  first-call (compile) cost from steady-state execute cost;
* ``memory``  — per-device ``memory_stats()`` snapshots at a cadence;
* ``profile`` — programmatic ``jax.profiler.trace`` windows over exactly
  the configured iterations (``obs_trace_iters=a:b`` + ``obs_trace_dir``);
* ``metrics`` — process-global counters/gauges/histograms with
  Prometheus-textfile and JSON export (``obs_metrics_path`` /
  ``obs_metrics_every``);
* ``health``  — non-finite guards, EMA loss divergence/plateau, memory
  watermark (``obs_health=off/warn/fatal``);
* ``compile`` — XLA compile-cache introspection: per-entry compile
  counts, signature diffs naming the offending axis, cost/memory
  analysis (``obs_compile=true`` -> schema-v3 ``compile_attr`` events);
* ``straggler`` — sampled per-shard arrival-skew profiling of the
  distributed learners (``obs_straggler_every`` /
  ``obs_straggler_warn_skew``);
* ``model``   — model observability: per-tree ``split_audit`` events
  (every realized split + the runner-up feature/gain margin from the
  split search) and top-k sparse ``importance`` evolution events
  (``obs_split_audit`` / ``obs_importance_every`` /
  ``obs_importance_topk``), read back via ``Booster.importance_history``;
* ``dataquality`` — dataset profiling at construction: per-feature
  missing rate, bin-occupancy entropy, constant/near-constant and
  high-cardinality flags, label balance — emitted as a ``data_profile``
  event and routed through the health channel so a degenerate dataset
  fails fast under ``obs_health=fatal``;
* ``roofline`` — roofline attribution: a device-peak registry (per
  ``device_kind`` FLOP/s, HBM and ICI bandwidth, VMEM — with a ``cpu``
  row so the layer is testable off-TPU) joined against the
  ``compile_attr`` cost estimates and measured execute times to give
  every jitted entry achieved-vs-peak utilization, arithmetic
  intensity, a compute/memory/collective/host-orchestration bound and
  headroom seconds; emits the per-iteration ``utilization`` rollup
  (``obs_utilization_every``, schema 13);
* ``live``    — the in-run live telemetry plane (``obs_http_port`` /
  ``obs_http_addr``): a stdlib ThreadingHTTPServer daemon serving
  ``/metrics`` (Prometheus), ``/healthz`` (200/503), ``/statusz``
  (JSON run snapshot) and ``/events?after=N`` (ring-buffer JSONL tail)
  from host-side observer state only — zero hot-path syncs — plus the
  ``obs watch`` live-follow CLI over files, shard sets and URLs;
* ``drift``   — drift & online model-quality monitoring: at training
  time a per-feature binned fingerprint of the data world (histograms
  from the BinMapper sample + frozen mappers + training-score
  distribution + final eval snapshot) persists with the model text and
  the binned dataset dir; at serving time a ``DriftMonitor`` bins
  incoming traffic with the same frozen mappers into rolling windows,
  computing PSI/KS per feature and for the score distribution every
  ``obs_drift_every`` rows (schema-14 ``drift`` events,
  ``lgbm_drift_psi`` gauges, obs_health alerts), counts non-finite /
  out-of-range input anomalies, and joins delayed labels
  (``ServingPredictor.record_outcome``) into rolling online
  AUC/logloss vs the training reference (``online_quality`` events);
* ``incident`` — the incident engine (``obs_incident*``): taps every
  detector channel (health warn/fatal, SLO burn, straggler skew,
  watchdog near-expiry, steady-state recompiles, drift alerts, serve
  shed storms, operator POSTs), debounces co-occurring signals into one
  grouped incident (schema-15 ``incident_open`` / ``incident_evidence``
  / ``incident_close``), captures an evidence bundle at the moment of
  anomaly (ring slice, metrics snapshot, flight context, utilization
  rollup, /statusz snapshot, thread stacks, optional one-iteration
  armed profiler trace), and renders the ``obs incident`` triage
  report with cross-subsystem correlation and root-cause ranking;
* ``prof``    — continuous host sampling profiler (``obs_prof_hz``,
  default ~29 Hz, off at 0): a daemon thread walks
  ``sys._current_frames()`` on a jittered monotonic clock, folds each
  thread's stack into Brendan-Gregg collapsed-stack counts tagged with
  the live stage/phase/iteration/thread-role context, and rolls windows
  into schema-16 ``prof_profile`` events with a self-measured
  ``overhead_frac`` gated at <1%; read back via ``obs prof``
  (top-table, ``--flame`` HTML flamegraph, ``--check`` budget gate)
  and on demand via the live plane's ``GET /prof?seconds=N``;
* ``query``   — the one timeline reader behind ``python -m lightgbm_tpu
  obs summary|recompiles|stragglers|explain|roofline|serve|drift|
  incident|merge|diff|trace|watch|prof``;
* ``merge``   — cross-rank merge of per-rank timeline shards: barrier
  skew per host collective (aligned on ``seq``), per-rank phase
  comparison, slowest-rank attribution, and a merged critical-path
  timeline trace_summary/bench_compare ingest directly;
* ``watchdog`` — hang watchdog + flight recorder: no progress within
  ``obs_watchdog_secs`` (or SIGTERM, or an ``obs_health=fatal`` abort)
  dumps the event ring buffer, all thread stacks, device memory and a
  metrics snapshot to ``<events_path>.flight.json``;
* ``ledger``  — cross-run performance ledger: finished timelines land
  as per-run metric records in an append-only crash-safe store
  (``obs_ledger_dir`` / ``LGBM_TPU_LEDGER``), keyed by suite / shape /
  device kind + the run_header provenance (git rev, schema 10); rolling
  median/MAD baselines feed ``tools/bench_compare.py --baseline
  rolling`` and the ``obs history`` / ``obs trend --check`` CLI flags
  change-points attributed to the git rev that introduced them.

Distributed runs are rank-native (schema 4): each rank writes its own
timeline shard (``obs_events_path`` + ``.r{rank}``), every event
carries the rank, and the run header records rank/world_size/
coordinator.

Config surface (utils/config.py): ``obs_events_path``, ``obs_timing``,
``obs_memory_every``, ``obs_trace_iters``, ``obs_trace_dir``,
``obs_flush_every``, ``obs_fsync``, ``obs_health*``, ``obs_metrics*``,
``obs_compile``, ``obs_straggler_every``, ``obs_straggler_warn_skew``,
``obs_watchdog_secs``, ``obs_flight_events``, ``obs_split_audit``,
``obs_importance_every``, ``obs_importance_topk``, ``obs_data_profile``,
``obs_ledger_dir``, ``obs_ledger_suite``, ``obs_ledger_window``,
``obs_utilization_every``, ``obs_roofline_peaks``, ``obs_http_port``,
``obs_http_addr``, ``obs_drift_every``, ``obs_drift_window``,
``obs_drift_psi``, ``obs_drift_fingerprint``, ``obs_drift_topk``,
``obs_drift_min_labels``, ``obs_incident``, ``obs_incident_window_s``,
``obs_incident_dir``, ``obs_incident_trace``, ``obs_prof_hz``,
``obs_prof_window_s``, ``obs_prof_topk``.
See docs/Observability.md for the schema.
"""
from __future__ import annotations

from .events import (NULL_OBSERVER, SCHEMA_VERSION, EventWriter,
                     NullObserver, RingBuffer, RunObserver,
                     collect_provenance, current_observer, read_events,
                     resolve_rank_path, validate_event)
from .health import HealthMonitors
from .ledger import (Ledger, default_ledger_dir, metrics_from_events,
                     rolling_stats)
from .metrics import REGISTRY, MetricsRegistry
from ..utils.log import Log

__all__ = ["NULL_OBSERVER", "NullObserver", "RunObserver", "EventWriter",
           "RingBuffer", "SCHEMA_VERSION", "read_events", "validate_event",
           "current_observer", "resolve_rank_path", "collect_provenance",
           "observer_from_config", "HealthMonitors", "MetricsRegistry",
           "REGISTRY", "Ledger", "default_ledger_dir",
           "metrics_from_events", "rolling_stats"]

_TIMING_MODES = ("auto", "phase", "iter", "off")
_HEALTH_MODES = ("off", "warn", "fatal")


def observer_from_config(config, comm=None):
    """RunObserver from the ``obs_*`` config params, or NULL_OBSERVER when
    nothing is enabled — the disabled path must cost one attribute check.

    ``comm``: optional parallel.comm.HostComm — the observer then shards
    its timeline for that rank (``obs_events_path`` auto-suffixes
    ``.r{rank}``) and stamps every event with it.  Without a comm the
    rank is resolved from the thread's rank context (run_ranks) or
    jax.distributed, falling back to a rank-0 single-process run.

    ``obs_timing`` semantics: 'phase' fences every phase boundary with
    ``jax.block_until_ready`` (device-accurate per-phase times; breaks the
    async pipeline, so it costs throughput); 'iter' fences once per
    iteration (accurate per-iteration totals, phases are dispatch-only —
    the bench protocol); 'off' records wall times without any fencing
    (dispatch cost only); 'auto' = 'phase'.

    Any of ``obs_events_path`` / ``obs_trace_iters`` / ``obs_memory_every``
    / ``obs_health`` (non-off) / ``obs_metrics_path`` /
    ``obs_metrics_every`` / ``obs_compile`` / ``obs_straggler_every`` /
    ``obs_split_audit`` / ``obs_importance_every`` / ``obs_ledger_dir`` /
    ``obs_utilization_every`` / ``obs_drift_every`` / ``obs_incident``
    enables the observer; health, metrics, compile and model tracking
    work without an events path (in-memory timeline via
    Booster.telemetry()).  A non-empty ``obs_ledger_dir`` additionally
    ingests the finished run into the cross-run ledger on clean close.
    """
    events_path = str(getattr(config, "obs_events_path", "") or "")
    trace_iters = str(getattr(config, "obs_trace_iters", "") or "")
    memory_every = int(getattr(config, "obs_memory_every", 0) or 0)
    health_mode = str(getattr(config, "obs_health", "off")
                      or "off").strip().lower()
    if health_mode not in _HEALTH_MODES:
        Log.fatal("Unknown obs_health %s (expected off/warn/fatal)",
                  health_mode)
    metrics_path = str(getattr(config, "obs_metrics_path", "") or "")
    metrics_every = int(getattr(config, "obs_metrics_every", 0) or 0)
    compile_attr = bool(getattr(config, "obs_compile", False))
    straggler_every = int(getattr(config, "obs_straggler_every", 0) or 0)
    split_audit = bool(getattr(config, "obs_split_audit", False))
    importance_every = int(getattr(config, "obs_importance_every", 0) or 0)
    ledger_dir = str(getattr(config, "obs_ledger_dir", "") or "")
    utilization_every = int(getattr(config, "obs_utilization_every", 0)
                            or 0)
    drift_every = int(getattr(config, "obs_drift_every", 0) or 0)
    incident = bool(getattr(config, "obs_incident", False))
    # -1 = off; 0 is a real value (ephemeral port), so no `or` collapse
    http_port = getattr(config, "obs_http_port", -1)
    http_port = -1 if http_port is None else int(http_port)
    if (not events_path and not trace_iters and memory_every <= 0
            and health_mode == "off" and not metrics_path
            and metrics_every <= 0 and not compile_attr
            and straggler_every <= 0 and not split_audit
            and importance_every <= 0 and not ledger_dir
            and utilization_every <= 0 and http_port < 0
            and drift_every <= 0 and not incident):
        return NULL_OBSERVER
    timing = str(getattr(config, "obs_timing", "auto")).strip().lower()
    if timing not in _TIMING_MODES:
        Log.fatal("Unknown obs_timing %s (expected auto/phase/iter/off)",
                  timing)
    if timing == "auto":
        timing = "phase"
    trace_dir = str(getattr(config, "obs_trace_dir", "") or "")
    if trace_iters and not trace_dir:
        Log.fatal("obs_trace_iters requires obs_trace_dir (where the "
                  "jax.profiler trace is written)")
    health = None
    if health_mode != "off":
        health = HealthMonitors(
            mode=health_mode,
            every=int(getattr(config, "obs_health_every", 1) or 1),
            divergence=float(getattr(config, "obs_health_divergence",
                                     3.0) or 0.0),
            plateau=int(getattr(config, "obs_health_plateau", 0) or 0),
            mem_frac=float(getattr(config, "obs_health_mem_frac",
                                   0.9) or 0.0))
    rank = world_size = None
    coordinator = ""
    if comm is not None:
        rank, world_size = int(comm.rank), int(comm.size)
        coordinator = str(getattr(comm, "coordinator", "") or "")
    return RunObserver(events_path=events_path, timing=timing,
                       memory_every=memory_every, trace_iters=trace_iters,
                       trace_dir=trace_dir,
                       flush_every=int(getattr(config, "obs_flush_every",
                                               16) or 16),
                       health=health, metrics_every=metrics_every,
                       metrics_path=metrics_path,
                       compile_attr=compile_attr,
                       straggler_every=straggler_every,
                       straggler_warn_skew=float(
                           getattr(config, "obs_straggler_warn_skew",
                                   0.5) or 0.5),
                       rank=rank, world_size=world_size,
                       coordinator=coordinator,
                       fsync=bool(getattr(config, "obs_fsync", False)),
                       watchdog_secs=float(
                           getattr(config, "obs_watchdog_secs", 0.0)
                           or 0.0),
                       flight_events=int(
                           getattr(config, "obs_flight_events", 256)
                           or 256),
                       ledger_dir=ledger_dir,
                       ledger_suite=str(
                           getattr(config, "obs_ledger_suite", "")
                           or ""),
                       utilization_every=utilization_every,
                       roofline_peaks=str(
                           getattr(config, "obs_roofline_peaks", "")
                           or ""),
                       http_port=(http_port if http_port >= 0 else None),
                       http_addr=str(
                           getattr(config, "obs_http_addr", "127.0.0.1")
                           or "127.0.0.1"),
                       incident=incident,
                       incident_window_s=float(
                           getattr(config, "obs_incident_window_s", 5.0)
                           or 5.0),
                       incident_dir=str(
                           getattr(config, "obs_incident_dir", "") or ""),
                       incident_trace=bool(
                           getattr(config, "obs_incident_trace", False)),
                       # the profiler piggybacks on an otherwise-enabled
                       # observer; its default never flips the NULL
                       # short-circuit above
                       prof_hz=int(
                           getattr(config, "obs_prof_hz", 29) or 0),
                       prof_window_s=float(
                           getattr(config, "obs_prof_window_s", 5.0)
                           or 5.0),
                       prof_topk=int(
                           getattr(config, "obs_prof_topk", 20) or 20))
