"""repro.obs — unified tracing & metrics across the checkpoint lifecycle.

One accounting spine for what used to be ~10 scattered ``perf_counter``
sites and five disjoint stats dataclasses: spans (where did the time go),
counters (how many bytes/shards/hits), and instant events (fault-point
hits, tier fallbacks, invariant checks).  Disabled cost is one global
read + branch per call site — see ``trace.py``.

Usage::

    import repro.obs as obs

    with obs.enabled() as tracer:
        ...  # any save/restore/hot/serve work
        print(tracer.summary())
    obs.write_chrome_trace("trace.json", tracer)   # Perfetto-loadable

DESIGN.md §9 documents the span taxonomy and the export formats.
"""

from repro.obs.metrics import Metrics
from repro.obs.sinks import (
    chrome_trace,
    format_summary,
    validate_chrome_trace,
    write_chrome_trace,
)
from repro.obs.trace import (
    NULL_SPAN,
    Span,
    Tracer,
    active,
    add,
    attach,
    current,
    disable,
    enable,
    enabled,
    event,
    span,
    timed,
)

__all__ = [
    "Metrics",
    "NULL_SPAN",
    "Span",
    "Tracer",
    "active",
    "add",
    "attach",
    "chrome_trace",
    "current",
    "disable",
    "enable",
    "enabled",
    "event",
    "format_summary",
    "span",
    "timed",
    "validate_chrome_trace",
    "write_chrome_trace",
]
