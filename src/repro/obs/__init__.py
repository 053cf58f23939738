"""StreamTrace observability: structured tracing, host phase spans, work
counters, span assembly, exporters.

``repro.obs`` must stay import-light and engine-agnostic (the engine imports
it, not vice versa): recorders and span math are pure host-side Python over
values the engine already fetched.
"""
from repro.obs.counters import COUNTER_HELP, WorkCounters
from repro.obs.export import (
    PromRegistry,
    chrome_trace,
    engine_registry,
    save_chrome_trace,
)
from repro.obs.spans import compute_phases, request_phases
from repro.obs.trace import (
    EVENT_NAMES,
    EVENT_SCHEMAS,
    SCHEMA_VERSION,
    SPAN_NAMES,
    NullRecorder,
    TraceRecorder,
    make_recorder,
)

__all__ = [
    "COUNTER_HELP",
    "EVENT_NAMES",
    "EVENT_SCHEMAS",
    "SCHEMA_VERSION",
    "SPAN_NAMES",
    "NullRecorder",
    "PromRegistry",
    "TraceRecorder",
    "WorkCounters",
    "chrome_trace",
    "compute_phases",
    "engine_registry",
    "make_recorder",
    "request_phases",
    "save_chrome_trace",
]
