"""Spans the benchmark records around its calls into the program.

The program has no spans of its own, so the benchmark wraps each job it
submits: every block and every arrival warm-up is timed on the host clock
(``time.perf_counter``). In a traced run the same wrappers also emit a
``jax.profiler.TraceAnnotation``, so that the trace can put each idle gap
of the device down to what the host was doing.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import List, Optional

import jax

BLOCK = "chipbench.block"
WARMUP = "chipbench.warmup"
WINDOW_OPEN = "chipbench.window_open"
WINDOW_CLOSE = "chipbench.window_close"


@dataclass
class Span:
    job: int
    start: float
    end: float
    #: Tokens generated in the span, over the whole batch (blocks only).
    tokens: int = 0
    #: True for a job's first block, which runs its prefill.
    prefill: bool = False
    #: Decode steps run in the span, and how many the job ran before it.
    decode_steps: int = 0
    decode_start: int = 0


@dataclass
class JobRecord:
    """One job of the window, on the host clock."""

    index: int
    blocks: int
    seed: int
    due: float
    submitted: Optional[float] = None
    finished: Optional[float] = None
    failed: bool = False
    #: When the client gave up on a failed job.
    gave_up: Optional[float] = None
    #: The job's generated ``[batch]`` token arrays, in order
    #: (``ServeJob.generated``), kept for the output check.
    generated: Optional[list] = None

    @property
    def turnaround(self) -> float:
        return self.finished - self.due

    @property
    def ended(self) -> Optional[float]:
        """When the job's result came, or when the client gave up on it."""
        return self.finished if self.finished is not None else self.gave_up


@dataclass
class Recorder:
    trace: bool = False
    blocks: List[Span] = field(default_factory=list)
    warmups: List[Span] = field(default_factory=list)

    def _annotate(self, name: str, job: int):
        if self.trace:
            return jax.profiler.TraceAnnotation(name, job=job)
        return contextlib.nullcontext()

    def marker(self, name: str) -> float:
        """A point on the host clock, also written into the trace."""
        with self._annotate(name, -1):
            return time.perf_counter()

    def wrap(self, job, index: int, batch: int):
        """Record the blocks and the warm-up of ``job`` under ``index``."""
        make_block_fn, warmup_fn = job.make_block_fn, job.warmup_fn

        def wrapped_make_block_fn(residency: int):
            block = make_block_fn(residency)

            def timed_block():
                before = len(job.generated)
                with self._annotate(BLOCK, index):
                    t0 = time.perf_counter()
                    block()
                    t1 = time.perf_counter()
                new = len(job.generated) - before
                first = before == 0
                self.blocks.append(Span(
                    index, t0, t1, tokens=new * batch, prefill=first,
                    decode_steps=new - int(first),
                    decode_start=max(0, before - 1)))
            return timed_block

        def timed_warmup():
            with self._annotate(WARMUP, index):
                t0 = time.perf_counter()
                warmup_fn()
                t1 = time.perf_counter()
            self.warmups.append(Span(index, t0, t1, decode_steps=1))

        job.make_block_fn = wrapped_make_block_fn
        if warmup_fn is not None:
            job.warmup_fn = timed_warmup
        return job


class CompileCounter:
    """Counts programs readied inside a ``with`` block: backend compiles
    and loads from the persistent cache alike fire this event."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event == self._EVENT:
            self.count += 1

    def __enter__(self) -> "CompileCounter":
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)
