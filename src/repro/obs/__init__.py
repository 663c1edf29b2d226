"""Unified observability layer: structured events, metrics, exporters.

Every layer of the reproduction — the Monte-Carlo engine, the trace gym,
the elastic runtime, the serving engine, the policy evaluator, and the
benchmarks — reports through bespoke ledgers and ad-hoc JSON. This
package gives them one instrumentation seam:

``events``     typed spans/instants with dual wall/sim-clock timestamps,
               a ``Recorder`` that buffers them (JSONL sink), and a
               zero-cost ``NULL`` recorder every integration point
               defaults to.
``metrics``    labeled counters/gauges/histograms in a ``MetricsRegistry``
               (each ``Recorder`` carries one).
``export``     Chrome-trace/Perfetto JSON for timeline viewing (with
               cross-track flow arrows for trace-correlated requests),
               CSV and flat stats summaries compatible with
               ``benchmarks/common.emit(stats=)``.
``timeseries`` windowed ring-buffer time-series: labeled gauges sampled
               on a sim-clock cadence, JSONL/CSV export, plus the
               standard serving signal set (``attach_serve_cluster``).
``slo``        rolling SLO health: attainment/TTFT percentiles,
               multi-window burn rates, typed alerts the autoscaler
               consumes as a first-class scale-up signal.
``report``     self-contained HTML/text ops report (sparklines, alert
               table, per-replica summary) from the above artifacts.
``profiling``  ``jax.profiler`` bridge: ``region``/``annotate_span``
               name traced code in the compiled program, so device
               traces split by region; the only module here that touches
               jax, and not imported by this package.

The core modules (events/metrics/export) are dependency-light on purpose:
stdlib only, importable before jax, usable from the pure-NumPy simulation
stack without dragging in the training stack.
"""
from repro.obs.events import (CAT_BENCH, CAT_GYM, CAT_KERNEL,  # noqa: F401
                              CAT_POLICY, CAT_SERVE, CAT_SIM, CAT_TRAIN,
                              EV_ALERT, EV_COMPLETE, EV_DECODE, EV_DRAIN,
                              EV_ENQUEUE, EV_EPISODE, EV_MIGRATE, EV_PREFILL,
                              EV_REJECT, EV_REPLAN, EV_REVOKE_FIRE,
                              EV_REVOKE_WARN, EV_SLOT_JOIN, EV_SLOT_RELEASE,
                              EV_SLOT_REQUEST, EV_STEP, EV_TRIAL_DONE,
                              TAXONOMY, Event, NULL, NullRecorder, Recorder,
                              load_events, load_header)
from repro.obs.metrics import (Counter, Gauge, Histogram,  # noqa: F401
                               MetricsRegistry)
from repro.obs.export import (metrics_stats, perf_entry,  # noqa: F401
                              to_chrome_trace, validate_chrome_trace,
                              write_chrome_trace, write_events_csv)
from repro.obs.timeseries import (TimeSeries, TimeSeriesSampler,  # noqa: F401
                                  attach_serve_cluster, load_series_jsonl)
from repro.obs.slo import (ALERT_POOL_EXHAUSTION,  # noqa: F401
                           ALERT_REVOCATION_STORM, ALERT_SLO_BURN,
                           Alert, SLOMonitor, SLOSpec)
from repro.obs.report import (render_report, render_text,  # noqa: F401
                              validate_report)
