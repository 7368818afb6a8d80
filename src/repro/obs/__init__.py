"""repro.obs — structured telemetry for the FL engine.

A metrics registry (counters / gauges / histograms / per-block tallies)
plus a span tracer over the simulation's **virtual clock** and the host
wall clock, fanned out to pluggable sinks (in-memory, JSONL,
Perfetto/Chrome ``trace_event`` export).  Off by default
(``FLConfig.telemetry="off"`` routes every call to the no-op
:data:`NOOP` recorder); when enabled, instrumented runs stay
bitwise-identical to uninstrumented ones — telemetry only *reads*
quantities the engine already computed.

Entry points::

    python -m repro.obs.report run_dir/events.jsonl   # run summary
    python -m repro.obs.trace  run_dir/events.jsonl t.json  # Perfetto
    python -m repro.obs.smoke                          # CI end-to-end

Wall-clock span names and meanings are in :data:`SPANS`; see
``docs/OBSERVABILITY.md`` for the metric catalog.
"""

from repro.obs.coverage import coverage_table, format_coverage
from repro.obs.recorder import (NOOP, NoopRecorder, Recorder, build_recorder,
                                metric_key, runtime_provenance)
from repro.obs.schema import validate_event, validate_events, validate_file
from repro.obs.sinks import JsonlSink, MemorySink, Sink, load_events
from repro.obs.spans import SPANS
from repro.obs.trace import export_trace, to_trace_events

__all__ = [
    "Recorder", "NoopRecorder", "NOOP", "build_recorder", "metric_key",
    "runtime_provenance",
    "Sink", "MemorySink", "JsonlSink", "load_events",
    "validate_event", "validate_events", "validate_file",
    "to_trace_events", "export_trace",
    "coverage_table", "format_coverage", "SPANS",
]
