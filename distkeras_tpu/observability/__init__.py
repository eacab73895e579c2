"""Observability: end-to-end tracing + the unified metrics surface.

The flight recorder the ROADMAP directions (train→serve streaming,
SLO-aware scheduling, shm transport) are debugged against:

- :mod:`distkeras_tpu.observability.trace` — zero-cost-when-off spans
  (thread-local ring buffers, monotonic clocks) emitting Chrome
  trace-event JSON loadable in Perfetto, with a correlation id
  (worker id + seqno, or serving request id) stitching one EXCHANGE
  across the worker thread, the PS handler, the WAL flusher, chain
  replicas, and the native C++ server ring. At the boundaries that
  feed the chip (``MeshTrainer.train``, the serving engine's loop) the
  same ``span()`` call has two more sinks: the PROFILER SINK
  (``profile=True``: a ``jax.profiler.TraceAnnotation`` on the device
  trace's clock) and the RUN LOG (``log=True``; ``trace.run_log()``:
  set-up phases, epoch ends and every JAX trace, lower and compile,
  kept with tracing off).
- :mod:`distkeras_tpu.observability.programs` — the compiled step's
  operations by the program's own scopes: a handle on the step
  ``SPMDEngine.run_step`` ran (``note``), from which ``op_scopes`` makes,
  on demand, the table ``{HLO instruction: (scope path, pass)}`` that
  lays a profiler trace's device time on modules, ``jax.named_scope``s
  and passes (forward, remat's forward, backward); its one run-log span
  is ``program.op_scopes``.
- :mod:`distkeras_tpu.observability.metrics` — a typed registry
  normalizing ``ps.stats()`` / serving / WAL counters into named
  metrics with Prometheus text + JSON snapshot exporters, served live
  via the ``metrics`` wire action on ``SocketParameterServer`` and
  ``GenerationServer``, plus the single-document
  :func:`~distkeras_tpu.observability.metrics.health_snapshot`.
- :mod:`distkeras_tpu.observability.timeseries` — the embedded
  time-series store (fixed-capacity downsampling ring series) and the
  background :class:`~distkeras_tpu.observability.timeseries.Scraper`
  sampling the PR 11 metrics surface into series over time.
- :mod:`distkeras_tpu.observability.watch` — the watchtower (ISSUE 13):
  declarative typed alert rules (τ p95, commit-rate skew, dup/fenced
  spikes, WAL fsync tails, shm ring occupancy, per-class serving SLO,
  loss-slope convergence stall) evaluated over those series, plus the
  ONE shared definition of rounds/s + straggler ratio that
  ``ElasticPolicy`` reads too.
- :mod:`distkeras_tpu.observability.analyze` — the analyst (ISSUE 14):
  post-hoc critical-path attribution over the recorded spans (per-worker
  waterfalls, pipelining overlap efficiency, center-lock/fsync/straggler
  wait attribution) ending in a typed regime verdict
  (compute/wire/fsync/fold-lock/host-core-bound) with knob-keyed
  recommendations; ``analyze=True`` on a trainer runs it post-run into
  ``trainer.analysis_``, and ``regime_source`` feeds the live regime
  series the watchtower's ``BottleneckShiftRule`` fires on.
- ``python -m distkeras_tpu.observability`` — ``dump`` / ``tail`` a
  live server's metrics, emit the ``health`` snapshot, ``health
  --watch`` a live server's alert transitions, or ``analyze`` a saved
  trace into the bottleneck report, or list its longest ``steps``
  (``train.step`` / ``serve.step`` with their children, totals by
  program key).

Trainer knobs: ``trace=True`` (enable), ``trace_dir=`` (write the
timeline file, path lands in ``trainer.trace_path_``),
``trace_sample=`` (deterministic span sampling); ``watch=True`` /
``watch_rules=`` / ``watch_dir=`` / ``scrape_interval=`` /
``watch_hook=`` run the watchtower over a training run (alerts land in
``trainer.watch_alerts_``, the dump path in ``trainer.watch_path_``).
"""

from distkeras_tpu.observability import analyze, timeseries, trace, watch
from distkeras_tpu.observability.metrics import (
    MetricsRegistry,
    health_snapshot,
    phase_metrics,
    ps_metrics,
    serving_metrics,
    trace_metrics,
    training_metrics,
)
from distkeras_tpu.observability.timeseries import Scraper, TimeSeriesStore
from distkeras_tpu.observability.watch import (
    Watchdog,
    Watchtower,
    default_rules,
)

__all__ = [
    "trace", "timeseries", "watch", "analyze", "MetricsRegistry", "ps_metrics",
    "serving_metrics", "phase_metrics", "trace_metrics", "training_metrics",
    "health_snapshot", "TimeSeriesStore", "Scraper", "Watchdog",
    "Watchtower", "default_rules",
]
