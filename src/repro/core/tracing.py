"""Spans and counters of the served path, on the host clock.

The tracer is process-wide and off by default, like ``jax.profiler``. It
records while it is started (:func:`start` ... :func:`stop`) and while a
profiler trace is being captured (``jax.profiler.trace`` or
``start_trace``), so that a captured profile always comes with the
program's own records. When it records:

* :func:`span` records ``(name, start, end, thread, args)`` on
  ``time.perf_counter`` and enters ``jax.profiler.TraceAnnotation(name,
  **args)``, so that the span also lands on the trace's host plane, on the
  clock of the device planes;
* :func:`count` records a point with a value, also written into the trace;
* :func:`interval` records a span its caller timed, possibly across
  threads (the records hold it, the trace does not);
* :func:`sample_memory` counts the default device's allocator state.

When it does not, :func:`span` returns one shared null context and the
others return at once: no annotation is built, no list grows and
``memory_stats()`` is never called. Code between a block's end and the
next block's start, where the device waits for the host, tests
:func:`enabled` once instead.

Names follow ``<layer>.<what>``; ``args`` carry the job key (``job``) or
block index where there is one. What each name means, and which metric
reads it, is listed in the README's "Tracing" section.
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time
from typing import Dict, Iterator, List, NamedTuple

__all__ = [
    "Record",
    "count",
    "enabled",
    "interval",
    "records",
    "sample_memory",
    "span",
    "start",
    "stop",
]


class Record(NamedTuple):
    """One span or count (a count has ``start == end`` and its value under
    ``args["value"]``)."""

    name: str
    start: float
    end: float
    thread: str
    args: Dict


_started = False
#: ``jax.profiler.TraceAnnotation.is_enabled``, once JAX is imported.
_is_profiling = None
_records: List[Record] = []
_lock = threading.Lock()
_NULL = contextlib.nullcontext()
#: Characters that the profiler's annotation encoding reserves.
_RESERVED = str.maketrans("#,=", "___")


def enabled() -> bool:
    """Whether the tracer records: started, or a profiler trace is being
    captured (never without JAX). Hot paths test this once and skip their
    records' arguments when it is false."""
    global _is_profiling
    if _started:
        return True
    if _is_profiling is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return False
        _is_profiling = jax.profiler.TraceAnnotation.is_enabled
    return _is_profiling()


def start() -> None:
    """Record until :func:`stop`, with or without a profiler trace."""
    global _started
    _started = True


def stop() -> List[Record]:
    """Stop recording; return every record so far and forget them."""
    global _started, _records
    _started = False
    with _lock:
        out, _records = _records, []
    return sorted(out, key=lambda r: r.start)


def records() -> List[Record]:
    """Every record so far, by start, without forgetting them."""
    with _lock:
        out = list(_records)
    return sorted(out, key=lambda r: r.start)


def _annotation(name: str, args: Dict):
    import jax
    return jax.profiler.TraceAnnotation(name, **{
        k: v.translate(_RESERVED) if isinstance(v, str) else v
        for k, v in args.items()})


def _append(name: str, start: float, end: float, args: Dict) -> None:
    record = Record(name, start, end, threading.current_thread().name, args)
    with _lock:
        _records.append(record)


@contextlib.contextmanager
def _recorded(name: str, args: Dict) -> Iterator[None]:
    with _annotation(name, args):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            _append(name, t0, time.perf_counter(), args)


def span(name: str, **args):
    """A context manager that records the time its body takes."""
    if not enabled():
        return _NULL
    return _recorded(name, args)


def count(name: str, value, **args) -> None:
    """Record ``value`` at this moment."""
    if not enabled():
        return
    args = dict(args, value=value)
    with _annotation(name, args):
        t = time.perf_counter()
    _append(name, t, t, args)


def interval(name: str, start: float, end: float, **args) -> None:
    """Record a span from ``start`` to ``end`` (``time.perf_counter``)."""
    if enabled():
        _append(name, start, end, args)


def sample_memory(name: str, **args) -> None:
    """Count the default device's ``bytes_in_use``, with
    ``largest_free_block_bytes``; nothing where the device keeps no
    allocator statistics (the CPU)."""
    if not enabled():
        return
    import jax
    stats = jax.local_devices()[0].memory_stats()
    if stats:
        count(name, stats["bytes_in_use"],
              largest_free_block_bytes=stats.get("largest_free_block_bytes"),
              **args)
