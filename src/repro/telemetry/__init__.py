"""Observability layer: device-resident round records, structured JSONL
traces with protocol-model byte cross-checks, and profiler hooks.

* ``telemetry.record`` — :class:`RoundTelemetry` / :class:`TelemetryCarry`
  pytrees that ride ``round_step``'s info dict and the scan carry (zero
  host syncs; one post-run fetch).
* ``telemetry.trace`` — stable JSONL event schema, :func:`build_trace`
  assembly with loud :class:`TelemetryMismatch` on any divergence from the
  ``core.protocol`` byte models, :func:`summarize` rollups, streaming
  :class:`TraceWriter` for tuner sweeps.
* ``telemetry.profile`` — ``jax.named_scope`` kernel labels keyed like the
  autotune table, beside the round body's own device scopes; the
  simulator driver's host spans are ``TraceAnnotation``s.
* ``telemetry.report`` — CLI rendering round tables and per-kind rollups
  from a trace file (``python -m repro.telemetry.report trace.jsonl``).
* ``telemetry.smoke`` — the CI smoke: a tiny traced federation written,
  validated and cross-checked end to end.
"""
from repro.telemetry.record import (  # noqa: F401
    RoundTelemetry, TelemetryCarry, build_round_record,
)
from repro.telemetry.trace import (  # noqa: F401
    SCHEMA_VERSION, TelemetryMismatch, TraceSummary, TraceWriter,
    build_trace, read_trace, round_bytes, summarize, trace_meta,
    validate_event, validate_trace, write_trace,
)
from repro.telemetry.profile import (  # noqa: F401
    kernel_scope, scope_name,
)
