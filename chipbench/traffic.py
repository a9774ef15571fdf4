"""The one traffic generator: reads a mix's parameters, draws from the seed.

A mix (``traffic/<name>.json``) gives the job classes, each with its number
of decode blocks and its count in every cycle, the batch and prompt length
of every job, and the arrival process:

* ``{"kind": "closed", "clients": C}``: C clients each keep one job in
  flight and send the next when its result comes.
* ``{"kind": "poisson", "rate_per_s": r}``: an open loop at r jobs a second.
* ``{"kind": "onoff", "rate_per_s": r, "on_mean_s": a, "off_mean_s": b}``:
  an open loop that sends only in on periods, at r (a + b) / a jobs a
  second, with exponential on and off periods of means a and b.

The run's seed draws each job's prompt (and the weights); the mix's own
``order_seed`` draws the order of the jobs and of their gaps, so that every
run seed sends the same work in the same order and loads the chip alike.
Each cycle holds every class its number of times in a shuffled order.
Exponential gaps and periods are not sampled freely: a cycle of n of them
takes the n midpoint quantiles of the distribution, rescaled to its mean
and shuffled, so every cycle of jobs spans the same time. (Drawn from the
run seed instead, the order moved the 90th percentile of turnaround by
about 20% between seeds of a 30 s window, while two runs of one seed
agreed within 2%.)
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Dict, Iterator, List

import numpy as np

#: Stream identifiers under one run seed, so that no two draws share bits.
CLASSES, GAPS, PERIODS, JOB_SEEDS, WEIGHTS, SETUP, SAMPLE = range(7)


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed % 2**64,
                                                         stream]))


def derived_seed(seed: int, stream: int, index: int = 0) -> int:
    """A seed below 2**31 (what the program's PRNG keys take whole)."""
    ss = np.random.SeedSequence([seed % 2**64, stream, index])
    return int(ss.generate_state(1)[0] % 2**31)


@dataclass(frozen=True)
class JobDraw:
    """One job of the mix: its place in the sequence, its class and seed."""

    index: int
    blocks: int
    seed: int
    #: Due time in seconds after the window opens (open loops only).
    due: float = 0.0


def cycle_classes(traffic: Dict) -> List[int]:
    out: List[int] = []
    for c in traffic["classes"]:
        out += [int(c["blocks"])] * int(c["per_cycle"])
    return out


def exponential_cycle(mean: float, n: int) -> np.ndarray:
    """The n midpoint quantiles of an exponential of this mean, rescaled
    so that they sum to n times the mean."""
    q = -np.log1p(-(np.arange(n) + 0.5) / n)
    return q * (n * mean / q.sum())


def _order(traffic: Dict) -> int:
    return int(traffic.get("order_seed", 0))


def classes(traffic: Dict) -> Iterator[int]:
    """Job classes (blocks per job) in sequence order, cycle by cycle."""
    base = cycle_classes(traffic)
    r = rng(_order(traffic), CLASSES)
    while True:
        yield from (base[i] for i in r.permutation(len(base)))


def _gaps(mean: float, n: int, r: np.random.Generator) -> Iterator[float]:
    cycle = exponential_cycle(mean, n)
    while True:
        yield from cycle[r.permutation(n)]


def arrival_times(traffic: Dict) -> Iterator[float]:
    """Due times, in seconds after the window opens, of an open loop."""
    arr = traffic["arrivals"]
    seed = _order(traffic)
    n = len(cycle_classes(traffic))
    kind = arr["kind"]
    if kind == "poisson":
        t = 0.0
        for gap in _gaps(1.0 / float(arr["rate_per_s"]), n,
                         rng(seed, GAPS)):
            t += gap
            yield t
    elif kind == "onoff":
        on_mean, off_mean = float(arr["on_mean_s"]), float(arr["off_mean_s"])
        burst_rate = float(arr["rate_per_s"]) * (on_mean + off_mean) / on_mean
        periods = int(arr.get("periods_per_cycle", 5))
        r = rng(seed, PERIODS)
        ons, offs = _gaps(on_mean, periods, r), _gaps(off_mean, periods, r)
        # Arrivals run on a clock that advances only in on periods; each
        # off period is inserted where an on period ends.
        busy, wall_offset, on_end = 0.0, 0.0, next(ons)
        for gap in _gaps(1.0 / burst_rate, n, rng(seed, GAPS)):
            busy += gap
            while busy >= on_end:
                wall_offset += next(offs)
                on_end += next(ons)
            yield busy + wall_offset
    else:
        raise ValueError(f"{kind!r} arrivals have no due times")


def open_schedule(traffic: Dict, seed: int, seconds: float) -> List[JobDraw]:
    """Every job of an open loop that falls due inside the window."""
    out = []
    for i, (due, blocks) in enumerate(zip(arrival_times(traffic),
                                          classes(traffic))):
        if due >= seconds:
            break
        out.append(JobDraw(i, blocks, derived_seed(seed, JOB_SEEDS, i), due))
    return out


def closed_sequence(traffic: Dict, seed: int) -> Iterator[JobDraw]:
    """The jobs that closed-loop clients take, in turn, without end."""
    for i, blocks in zip(itertools.count(), classes(traffic)):
        yield JobDraw(i, blocks, derived_seed(seed, JOB_SEEDS, i))
