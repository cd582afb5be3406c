"""Host spans on the profiler's clock.

``span("serve.pad")`` marks a phase of the host path as the event
``repro.serve.pad`` on the profiler's ``/host:CPU`` plane, which shares
its clock with the device planes: an idle stretch of a device is then
named by what the host was doing in it.  Keyword attributes
(``request=7``) arrive as the event's stats and leave its name alone.

A span records only while a profiler session runs (``jax.profiler.
start_trace``); the profiler's buffer is its only store.  Otherwise it
costs about a microsecond, so spans mark phases of a request, never the
trips of a loop.
"""
from __future__ import annotations

import jax.profiler

PREFIX = "repro."


def span(name: str, **attrs) -> jax.profiler.TraceAnnotation:
    """Context manager that records ``repro.<name>`` while it is open."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **attrs)
