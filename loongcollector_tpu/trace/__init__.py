"""loongtrace: end-to-end pipeline tracing (docs/observability.md).

Off by default; `enable()` / `LOONG_TRACE=1` turns it on.  Every hook in
this package is a single module-global read + branch when disabled —
scripts/trace_overhead.py gates that contract.
"""

from .tracer import (ENV_ENABLE, ENV_SAMPLE, ENV_SEED, VOLATILE_SPANS, Span,
                     TraceConfig, TraceEvent, Tracer, active, active_tracer,
                     current_span, disable, enable, event, install_from_env,
                     is_active, span, start_span, status)

__all__ = [
    "ENV_ENABLE", "ENV_SAMPLE", "ENV_SEED", "VOLATILE_SPANS", "Span",
    "TraceConfig", "TraceEvent", "Tracer", "active", "active_tracer",
    "current_span", "disable", "enable", "event", "install_from_env",
    "is_active", "span", "start_span", "status",
]
