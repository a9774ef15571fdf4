"""The readers of the program's own records (``chipbench/program.py``) on a
hand-made run, with hand-worked values, and on a program without the
tracer."""

import json
import sys

import pytest

from chipbench import catalog, harness
from chipbench.spans import Recorder

from repro.core import tracing
from repro.core.tracing import Record

NAMES = ("handoff_wait_s", "schedule_host_us", "predictor_error",
         "hbm_free_min_gib")
TRAFFIC = {"batch": 8, "prompt_len": 512,
           "classes": [{"blocks": 2, "per_cycle": 1}],
           "arrivals": {"kind": "closed", "clients": 1}}
T = "scheduler-service"


def _ctx():
    """A window from 10 s to 12 s on the host clock."""
    cfg = json.loads(
        (catalog.BENCH_DIR / "configs" / "yi-6b-8L.json").read_text())
    cell = catalog.Cell(name="t", chips=1, config=cfg, traffic=TRAFFIC,
                        checks=None)
    return harness.Context(cell=cell, seconds=2.0, t0=10.0, t1=12.0,
                           setup_s=3.0, jobs=[], recorder=Recorder(),
                           device_kind="TPU v5 lite")


def _span(name, start, end, **args):
    return Record(name, start, end, T, args)


def _count(name, t, value, **args):
    return Record(name, t, t, T, dict(args, value=value))


RECORDS = [
    # Job a#0, submitted before the window: its hand-off does not count.
    _span("service.handoff", 9.9, 10.0, job="a#0"),
    # Job b#1: 3 blocks, the first with the prefill, 0.3 s then 0.2 s
    # twice: 0.7 s of service; after one block Eq. 2 says 3 x 0.3 = 0.9.
    _span("service.handoff", 10.0, 10.3, job="b#1"),
    _span("executor.schedule", 10.3, 10.3001, job="b#1"),
    _span("executor.block", 10.3, 10.6, job="b#1", block=0, residency=1),
    _span("executor.schedule", 10.6, 10.6003, job="b#1"),
    _count("predictor.total_s", 10.6, 0.9, job="b#1", lane=0, done=1,
           blocks=3),
    _count("hbm.sample", 10.6, 12 * 2**30, jobs_held=2,
           largest_free_block_bytes=2**30),
    _span("executor.block", 10.7, 10.9, job="b#1", block=1, residency=1),
    _count("predictor.total_s", 10.9, 0.9, job="b#1", lane=0, done=2,
           blocks=3),
    _count("hbm.sample", 10.9, 12 * 2**30, jobs_held=2,
           largest_free_block_bytes=2**29),
    _span("executor.block", 11.0, 11.2, job="b#1", block=2, residency=1),
    # Job c#2: one block, so no error is read from it; submitted 11.1.
    _span("service.handoff", 11.1, 11.2, job="c#2"),
    _span("executor.block", 11.2, 11.4, job="c#2", block=0, residency=1),
    _span("executor.schedule", 11.4, 11.4002, job="c#2"),
    _count("predictor.total_s", 11.4, 0.2, job="c#2", lane=0, done=1,
           blocks=1),
    # Job d#3: 2 blocks, only its first recorded before the tracer
    # stopped, so no error is read from it.
    _span("executor.block", 11.8, 11.95, job="d#3", block=0, residency=1),
    _count("predictor.total_s", 11.95, 0.3, job="d#3", lane=0, done=1,
           blocks=2),
    # After the window.
    _count("hbm.sample", 12.5, 13 * 2**30, jobs_held=4,
           largest_free_block_bytes=2**20),
]


def _read(name, ctx):
    return catalog.load_module("metrics", name).read(ctx)


@pytest.fixture
def recorded(monkeypatch):
    monkeypatch.setattr(tracing, "records", lambda: list(RECORDS))


def test_readers_of_the_program_records(recorded):
    ctx = _ctx()
    assert _read("handoff_wait_s", ctx) == pytest.approx((0.3 + 0.1) / 2)
    # 0.6 ms of scheduling over the 5 blocks that started in the window.
    assert _read("schedule_host_us", ctx) == pytest.approx(600 / 5)
    assert _read("predictor_error", ctx) == pytest.approx(
        100 * 0.2 / 0.7)
    assert _read("hbm_free_min_gib", ctx) == pytest.approx(0.5)


def test_readers_read_nothing_without_records(monkeypatch):
    monkeypatch.setattr(tracing, "records", lambda: [])
    for name in NAMES:
        assert _read(name, _ctx()) is None


def test_readers_read_nothing_from_a_program_without_the_tracer(
        recorded, monkeypatch):
    # A module set to None in sys.modules fails its import.
    import repro.core
    monkeypatch.delattr(repro.core, "tracing")
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    for name in NAMES:
        assert _read(name, _ctx()) is None


def test_the_metrics_are_read_in_the_served_cells():
    cells = {"yi6b.mixed.poisson", "nemo12b.mixed.closed4",
             "nemo12b.mixed.bursty"}
    entries = {m["name"]: m for m in catalog.load_benchmark()["per_layer"]}
    for name in NAMES:
        assert cells <= set(entries[name]["workloads"])
        assert entries[name]["moves"] == "antt"
        assert (catalog.BENCH_DIR / "metrics" / f"{name}.py").is_file()
