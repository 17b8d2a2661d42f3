"""Unified run telemetry shared by both engines (see README.md here).

Typical wiring::

    from repro.telemetry import JsonlSink, MetricsRecorder

    rec = MetricsRecorder(
        sinks=[JsonlSink("run.jsonl")], metrics_every=5, record_spans=True
    )
    rec.manifest({"topology": topo.describe(), ...})
    sim = DecentralizedSimulator(..., telemetry=rec)

Then ``python -m repro.telemetry summarize run.jsonl``.

Spans and scopes that must share the device's clock go to the profiler
through ``repro.telemetry.profile``.
"""
from repro.telemetry import profile
from repro.telemetry.recorder import (
    MetricsRecorder, coalesce_into, host_grad_norm,
)
from repro.telemetry.schema import (
    KINDS, SCHEMA_VERSION, SchemaError, validate_record,
)
from repro.telemetry.sinks import JsonlSink, MemorySink, read_jsonl
from repro.telemetry.summarize import (
    diff_summaries, render_summary, summarize,
)

__all__ = [
    "profile",
    "MetricsRecorder",
    "JsonlSink",
    "MemorySink",
    "read_jsonl",
    "SCHEMA_VERSION",
    "SchemaError",
    "KINDS",
    "validate_record",
    "coalesce_into",
    "host_grad_norm",
    "summarize",
    "render_summary",
    "diff_summaries",
]
