"""The program's tracer (``repro.core.tracing``) and the spans and counters
of the served path, on jobs whose blocks sleep for a few milliseconds."""

import asyncio
import time

import jax
import pytest

from repro.core import tracing
from repro.core.executor import ExecutorJob, LaneExecutor
from repro.core.policies import make_policy
from repro.core.predictor import staircase_runtime
from repro.core.scheduler_service import SchedulerService


def sleep_job(name, blocks, per_block=0.002):
    def mk(residency):
        def block():
            time.sleep(per_block)
        return block
    return ExecutorJob(name=name, num_blocks=blocks, max_residency=1,
                       make_block_fn=mk)


class FakeDevice:
    """A device whose allocator statistics are counted when read."""

    def __init__(self):
        self.reads = 0

    def memory_stats(self):
        self.reads += 1
        return {"bytes_in_use": 7 * 2**30,
                "largest_free_block_bytes": 2**29}


@pytest.fixture
def watched(monkeypatch):
    """Counts the trace annotations built and the allocator reads."""
    built = []

    class CountingAnnotation(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kwargs):
            built.append(name)
            super().__init__(name, **kwargs)

    device = FakeDevice()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", CountingAnnotation)
    monkeypatch.setattr(jax, "local_devices", lambda: [device])
    tracing.stop()
    yield built, device
    tracing.stop()


def serve(jobs, policy="srtf", late=()):
    """Run ``jobs`` through a one-lane service, then ``late`` once the
    machine has run a block; returns the keys in submission order."""
    service = SchedulerService(n_lanes=1, policy=policy)
    handles = [service.submit(j) for j in jobs]
    if late:
        asyncio.run(service.wait_until_busy())
    handles += [service.submit(j) for j in late]
    for h in handles:
        h.result_blocking(timeout=10.0)
    service.close()
    return [h.key for h in handles]


def by_name(records, name):
    return [r for r in records if r.name == name]


def test_off_by_default_records_nothing_and_builds_nothing(watched):
    built, device = watched
    assert not tracing.enabled()
    serve([sleep_job("a", 3), sleep_job("b", 2)])
    with tracing.span("x.y", job="k#0"):
        pass
    tracing.count("x.count", 1)
    tracing.interval("x.interval", 0.0, 1.0)
    tracing.sample_memory("hbm.sample")
    assert tracing.records() == []
    assert built == []
    assert device.reads == 0
    assert tracing.span("a.b") is tracing.span("c.d")


def test_nested_spans_are_recorded_in_order_on_one_clock(watched):
    built, device = watched
    tracing.start()
    with tracing.span("outer.span", job="j#1"):
        time.sleep(0.002)
        with tracing.span("inner.span", block=3):
            time.sleep(0.002)
        tracing.count("inner.count", 5, job="j#1")
    tracing.sample_memory("hbm.sample", jobs_held=2)
    records = tracing.stop()
    assert [r.name for r in records] == ["outer.span", "inner.span",
                                         "inner.count", "hbm.sample"]
    outer, inner, point, memory = records
    assert outer.start <= inner.start < inner.end <= point.start
    assert point.start == point.end <= outer.end <= memory.start
    assert {r.thread for r in records} == {"MainThread"}
    assert outer.args == {"job": "j#1"} and inner.args == {"block": 3}
    assert point.args == {"job": "j#1", "value": 5}
    assert memory.args == {"value": 7 * 2**30,
                           "largest_free_block_bytes": 2**29,
                           "jobs_held": 2}
    assert device.reads == 1
    assert built == ["outer.span", "inner.span", "inner.count",
                     "hbm.sample"]
    assert not tracing.enabled() and tracing.records() == []


def test_a_profiler_trace_turns_the_tracer_on(tmp_path):
    tracing.stop()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert tracing.enabled()
        with tracing.span("traced.span"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert not tracing.enabled()
    with tracing.span("untraced.span"):
        pass
    assert [r.name for r in tracing.stop()] == ["traced.span"]


def test_a_service_run_records_every_job(watched):
    _, device = watched
    tracing.start()
    keys = serve([sleep_job("a", 3), sleep_job("b", 1)],
                 late=[sleep_job("c", 2)])
    records = tracing.stop()
    handoffs = {r.args["job"]: r for r in by_name(records, "service.handoff")}
    pickups = {r.args["job"]: r for r in by_name(records, "service.pickup")}
    assert set(handoffs) == set(pickups) == set(keys)
    for key in keys:
        assert handoffs[key].end - handoffs[key].start == pytest.approx(
            pickups[key].args["waited_s"])
        assert handoffs[key].end <= pickups[key].start
    blocks = by_name(records, "executor.block")
    assert sorted((r.args["job"], r.args["block"]) for r in blocks) == [
        ("a#0", 0), ("a#0", 1), ("a#0", 2), ("b#1", 0), ("c#2", 0),
        ("c#2", 1)]
    assert all(r.args["residency"] == 1 for r in blocks)
    scheduled = {r.args.get("job") for r in by_name(records,
                                                    "executor.schedule")}
    assert set(keys) <= scheduled
    assert by_name(records, "service.idle")
    assert {r.thread for r in blocks} == {"scheduler-service"}
    # One allocator sample at each block end.
    samples = by_name(records, "hbm.sample")
    assert len(samples) == len(blocks) == device.reads
    assert all(s.args["jobs_held"] >= 1 for s in samples)


def test_the_predictor_count_after_one_block_is_the_staircase(watched):
    tracing.start()
    ex = LaneExecutor([sleep_job("h", 5)], make_policy("srtf"), n_lanes=1)
    ex.run()
    records = tracing.stop()
    counts = by_name(records, "predictor.total_s")
    assert [c.args["done"] for c in counts] == [1, 2, 3, 4, 5]
    assert all(c.args["blocks"] == 5 and c.args["job"] == "h#0"
               for c in counts)
    _, _, t0, t1 = ex.trace[0]
    assert counts[0].args["value"] == pytest.approx(
        staircase_runtime(5, 1, t1 - t0))


def test_a_short_job_preempts_a_long_one_once_under_srtf(watched):
    tracing.start()
    long_key, short_key = serve([sleep_job("long", 20)],
                                late=[sleep_job("short", 2)])
    records = tracing.stop()
    preemptions = by_name(records, "scheduler.preemptions")
    assert [(p.args["job"], p.args["preempted"], p.args["value"])
            for p in preemptions] == [(short_key, long_key, 1)]


def test_fifo_runs_the_late_job_after_and_counts_no_preemption(watched):
    tracing.start()
    serve([sleep_job("long", 6)], policy="fifo",
          late=[sleep_job("short", 2)])
    assert by_name(tracing.stop(), "scheduler.preemptions") == []
