"""The traffic generator: one seed, one set of jobs and due times; every
seed the same work in another order."""

import json
from collections import Counter

import numpy as np
import pytest

from chipbench import traffic as tr
from chipbench.catalog import BENCH_DIR


def _mix(name):
    return json.loads((BENCH_DIR / "traffic" / f"{name}.json").read_text())


SEEDS = [0, 1, 2**31 + 7, 3 * 2**40 + 1]


@pytest.mark.parametrize("name", ["mixed_poisson", "mixed_bursty"])
def test_one_seed_gives_the_same_jobs_and_due_times(name):
    a = tr.open_schedule(_mix(name), 2**31 + 99, 120.0)
    b = tr.open_schedule(_mix(name), 2**31 + 99, 120.0)
    assert a == b and len(a) > 20


@pytest.mark.parametrize("name", ["mixed_poisson", "mixed_bursty"])
def test_every_seed_sends_the_same_work_in_the_same_order(name):
    """The run seed draws the prompts; the mix's order_seed the order."""
    a = tr.open_schedule(_mix(name), 2**31 + 99, 120.0)
    c = tr.open_schedule(_mix(name), 5, 120.0)
    assert [(j.due, j.blocks) for j in a] == [(j.due, j.blocks) for j in c]
    assert all(x.seed != y.seed for x, y in zip(a, c))
    other = dict(_mix(name), order_seed=2)
    d = tr.open_schedule(other, 5, 120.0)
    assert [j.blocks for j in d] != [j.blocks for j in c]


@pytest.mark.parametrize("seed", SEEDS)
def test_every_cycle_holds_the_mix(seed):
    mix = _mix("mixed_closed4")
    seq = [next(s) for s in [tr.closed_sequence(mix, seed)] for _ in range(50)]
    for cycle in range(5):
        blocks = [j.blocks for j in seq[10 * cycle:10 * cycle + 10]]
        assert Counter(blocks) == {1: 4, 2: 2, 4: 2, 16: 2}
    assert [j.index for j in seq] == list(range(50))
    assert len({j.seed for j in seq}) == 50
    other = [next(s) for s in [tr.closed_sequence(mix, seed + 1)]
             for _ in range(50)]
    assert [j.blocks for j in other] == [j.blocks for j in seq]
    assert all(0 <= j.seed < 2**31 for j in seq)


def test_poisson_cycles_span_the_same_time_under_every_seed():
    mix = _mix("mixed_poisson")
    rate = mix["arrivals"]["rate_per_s"]
    for seed in SEEDS:
        jobs = tr.open_schedule(mix, seed, 200.0)
        dues = [0.0] + [j.due for j in jobs]
        for k in range(1, len(dues) // 10):
            assert dues[10 * k] == pytest.approx(10 * k / rate)
        gaps = np.diff(dues[:11])
        assert sorted(gaps) == pytest.approx(
            sorted(tr.exponential_cycle(1 / rate, 10)))


def test_exponential_cycle_keeps_the_mean():
    cycle = tr.exponential_cycle(2.5, 10)
    assert cycle.mean() == pytest.approx(2.5)
    assert list(cycle) == sorted(cycle)
    assert cycle[-1] / cycle[0] > 30


def test_bursts_send_only_in_on_periods_at_the_mean_rate():
    mix = _mix("mixed_bursty")
    arr = mix["arrivals"]
    for seed in SEEDS:
        dues = np.array([j.due for j in tr.open_schedule(mix, seed, 3000.0)])
        assert len(dues) / 3000.0 == pytest.approx(arr["rate_per_s"],
                                                   rel=0.05)
        gaps = np.diff(dues)
        # Off periods of 8 s mean show as gaps far above the in-burst mean.
        burst = arr["on_mean_s"] / (arr["rate_per_s"] * (
            arr["on_mean_s"] + arr["off_mean_s"]))
        assert np.median(gaps) < 1.5 * burst
        assert (gaps > 4 * burst).sum() > 0.1 * len(dues) * burst * \
            arr["rate_per_s"]


def test_derived_seeds_differ_by_stream_and_fit_31_bits():
    seeds = {tr.derived_seed(2**33 + 5, s, i) for s in range(7)
             for i in range(20)}
    assert len(seeds) == 140 and max(seeds) < 2**31
