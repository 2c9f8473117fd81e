"""End-to-end request tracing & profiling for the serving stack.

Span trees from fleet admission down to PIM instruction streams, with
Chrome/Perfetto ``trace_event`` export and an in-process store tests
and the critical-path analyzer query directly.

Enable by attaching a `Tracer` (and optionally a `JsonEventLog`) to
the run's shared `MetricsRegistry`::

    metrics.tracer = Tracer()
    ex.serve(...)
    write_trace(metrics.tracer.store, "trace.json")

Absence of a tracer is the disabled state — span emission in the
runtime guards on ``metrics.tracer is None``, so a run without one
serves bit-for-bit the metrics of a build without this package
(regression-tested against a metrics golden).

Time-series telemetry (repro.obs.telemetry) rides the same contract on
``metrics.telemetry``: bounded counter/gauge/histogram series on the
caller's clock, exported as OpenMetrics text (repro.obs.openmetrics)
or Perfetto counter tracks merged into the trace JSON.

On the wall-clock ciphertext path, every layer boundary goes through
one hook (repro.obs.hook) that feeds the tracer and telemetry when
armed, and the JAX profiler and an always-on in-memory ring of layer
records in every run: that part is not free, about 2 us a layer and
0.6 us a generation-0 collection with nothing armed.
"""
from repro.obs.span import Span, SpanStore
from repro.obs.tracer import ExecObs, Tracer
from repro.obs.log import EVENTS, JsonEventLog
from repro.obs.perfetto import (to_trace_events, validate, validate_file,
                                write_trace)
from repro.obs.critical_path import (Segment, critical_path, request_chain,
                                     workload_breakdown)
from repro.obs.telemetry import (HistogramSeries, Series, SloBurnRate,
                                 Telemetry)
from repro.obs.openmetrics import render as render_openmetrics
from repro.obs.openmetrics import parse as parse_openmetrics
from repro.obs.openmetrics import write_metrics

__all__ = [
    "Span", "SpanStore", "Tracer", "ExecObs", "JsonEventLog", "EVENTS",
    "to_trace_events", "write_trace", "validate", "validate_file",
    "Segment", "critical_path", "request_chain", "workload_breakdown",
    "Telemetry", "Series", "HistogramSeries", "SloBurnRate",
    "render_openmetrics", "parse_openmetrics", "write_metrics",
]
