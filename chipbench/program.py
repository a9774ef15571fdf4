"""The program's own spans and counters (``repro.core.tracing``), which it
records while a profiler trace is captured: in a ``--trace 1`` run, from
just before the window opens to its close. A program without the tracer
has none, and the metrics that read them are then left out of the line."""

from __future__ import annotations

from typing import List


def records(name: str) -> List:
    """The program's records named ``name``, by start."""
    try:
        from repro.core import tracing
    except ImportError:
        return []
    return [r for r in tracing.records() if r.name == name]
