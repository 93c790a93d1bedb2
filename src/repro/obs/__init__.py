"""Unified telemetry: request-correlated tracing, metrics, exporters, SLOs.

BEYOND PAPER.  The paper's separation of concerns (frontend → IR → passes →
backends, §2.3) pays off operationally only when an operator can see *where*
time goes across the layers it separates.  Production deployments of this
toolchain family (PACE, the ESCAPE dwarfs) treat per-kernel timing and
scaling telemetry as first-class outputs; this package is that substrate:

* :mod:`repro.obs.trace` — structured span tracer: nested spans on ONE
  monotonic clock, bounded ring-buffer retention, a strict no-op fast path
  when disabled, per-request trace-id correlation (one batched dispatch
  span links every request that rode it), and the bridge that writes every
  span into a collecting ``jax.profiler`` session under its name.
* :mod:`repro.obs.sampling` — deterministic head-based trace sampling: the
  keep/drop decision is a pure hash of the request id, so the tracer can
  stay on in production at ``REPRO_TRACE_SAMPLE=0.1`` and a sampled-out
  request costs one hash check.  Error paths are always force-sampled.
* :mod:`repro.obs.metrics` — counters / gauges / streaming-quantile
  histograms behind a registry with Prometheus text export; the serving
  engine's ``stats()`` is a view of it and ``GET /metrics`` serves it.
* :mod:`repro.obs.slo` — declarative per-program service-level objectives
  evaluated with multi-window burn-rate math, plus the hysteresis-damped
  autoscaling recommendation served at ``GET /autoscale``.
* :mod:`repro.obs.flight` — the failure flight recorder: one self-contained
  JSON black box (spans + metrics + stats + config) dumped on worker death,
  crash-loop give-up, SLO breach, or SIGUSR2.
* :mod:`repro.obs.export` — Chrome-trace/Perfetto JSON dump + validation.

Instrumented layers: stencil build (frontend → passes → codegen → autotune),
program trace/compile, ensemble dispatch, and the full serving request
lifecycle (admit → queue → window → scatter → dispatch → gather → emit).
Everything is off by default and ≈ free while off; arm with ``REPRO_TRACE=1``,
``serve --trace-out``, or per call via ``exec_info={"trace": True}``.
See docs/observability.md for the span taxonomy and metric names.
"""

from . import export, flight, metrics, sampling, slo, trace
from .export import chrome_trace, validate_chrome_trace, write_chrome_trace
from .flight import FlightRecorder, load_bundle, validate_flight_bundle
from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .sampling import SamplingPolicy, head_sampled
from .slo import Autoscaler, BurnRule, Objective, SloEngine
from .trace import NOOP_SPAN, Span, Tracer, capture, configure, monotonic, span, use_tracer

__all__ = [
    "Autoscaler",
    "BurnRule",
    "Counter",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NOOP_SPAN",
    "Objective",
    "SamplingPolicy",
    "SloEngine",
    "Span",
    "Tracer",
    "capture",
    "chrome_trace",
    "configure",
    "export",
    "flight",
    "head_sampled",
    "load_bundle",
    "metrics",
    "monotonic",
    "sampling",
    "slo",
    "span",
    "trace",
    "use_tracer",
    "validate_chrome_trace",
    "validate_flight_bundle",
    "write_chrome_trace",
]
