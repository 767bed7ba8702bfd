"""``repro.obs`` — tracing, metrics and profiling for the serving stack.

The paper's headline numbers are throughput/latency/efficiency, and every
comparative photonic-accelerator claim rests on per-stage timing
attribution — so the serving engine gets a first-class observability
layer instead of a bag of mean-only counters:

* ``trace``   — nested spans over the engine's phases (step, schedule,
  prefill / chunk / decode / verify / defrag, page tables, token pulls),
  each also a ``jax.profiler`` annotation ``engine.<name>`` on the
  device trace's clock, exported as Chrome trace-event JSON
  (Perfetto-loadable) when recording is on.
* ``metrics`` — counters, gauges and log-bucketed histograms with exact
  percentile queries; the registry ``serving.EngineMetrics`` is built on.
* ``events``  — the structured scheduler event log: every admit / reject /
  evict / CoW-fork / defrag / spec-fallback decision with its reason,
  reassembled per-request as a queued→admitted→chunks→first-token→finished
  timeline (surfaced on ``api.RequestOutput``).
* ``profile`` — ``jax.profiler`` hooks wrapping N engine steps in a
  device trace (``--profile DIR``).
* ``config``  — ``ObsConfig`` (the ``RuntimeConfig.obs`` layer) and the
  ``Observability`` bundle the engine consumes; ``DISABLED`` is the
  shared null bundle.
* ``server``  — the live telemetry frontend: Prometheus text exposition
  over ``MetricsRegistry`` (histograms as native ``_bucket/_sum/_count``
  series), a grammar validator, and the stdlib ``MetricsServer`` serving
  ``/metrics`` + ``/healthz`` + ``/snapshot`` from a daemon thread.
* ``recorder`` / ``replay`` — the flight recorder: arm with
  ``ObsConfig(record_path=DIR)`` to capture a run (config fingerprint,
  arrival schedule, decision journal, token outputs, decision-clock
  tape) into a bundle that ``replay_bundle``/``LLM.replay``/``python -m
  repro.launch.replay`` reproduces bitwise offline, diffing any
  divergence to the first bad decision.  (``repro.obs.replay`` imports
  the api layer, so it is imported lazily, not re-exported here.)
* ``watchdog`` — the numerics watchdog: per-layer saturation / amax /
  quant-error / accumulator-headroom stats from every quantized GEMM,
  staged in-jit through ``jax.debug.callback`` (off: zero overhead; on:
  bitwise output-invisible).

Two invariants, test-asserted in ``tests/test_obs.py``: disabled
observability adds **zero overhead** (null sinks, no extra host syncs on
the decode path), and enabled observability is **output-invisible**
(greedy token streams stay bitwise identical with tracing on).
"""

from repro.obs import watchdog
from repro.obs.config import DISABLED, Observability, ObsConfig
from repro.obs.events import NULL_EVENTS, EventLog, NullEventLog
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               labeled, split_labels)
from repro.obs.profile import NULL_PROFILER, NullStepProfiler, StepProfiler
from repro.obs.recorder import FlightRecorder
from repro.obs.server import (MetricsServer, render_exposition,
                              validate_exposition)
from repro.obs.trace import NULL_TRACER, NullTracer, Span, Tracer

__all__ = [
    "Counter",
    "DISABLED",
    "EventLog",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "MetricsServer",
    "NULL_EVENTS",
    "NULL_PROFILER",
    "NULL_TRACER",
    "NullEventLog",
    "NullStepProfiler",
    "NullTracer",
    "ObsConfig",
    "Observability",
    "Span",
    "StepProfiler",
    "Tracer",
    "labeled",
    "render_exposition",
    "split_labels",
    "validate_exposition",
    "watchdog",
]
