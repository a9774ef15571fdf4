"""The harness body, end to end on the CPU at tiny widths: every run
prints a last line with exactly the keys the benchmark's contract names."""

import json

import pytest
from tinybench import run_tiny

LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]
END_TO_END = {"tokens_per_s", "turnaround_p90_s", "antt", "setup_s"}


@pytest.mark.parametrize("cell", ["tiny.closed2", "tiny.poisson"])
def test_run_prints_the_contract_line(bench_copy, cell):
    out = run_tiny(bench_copy, cell)
    line = json.loads(json.dumps(out.line))
    assert list(line) == LINE_KEYS
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    # peak_hbm_gib reads the device's allocator, which the CPU lacks.
    assert set(line["metrics"]) == END_TO_END
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["checks"]["widest_logit_gap"]["value"] <= \
        line["checks"]["widest_logit_gap"]["limit"]


def test_open_loop_sends_every_job_due_and_times_it_from_its_due_time(
        bench_copy):
    out = run_tiny(bench_copy, "tiny.poisson", seconds=2.0)
    jobs = out.context.jobs
    # 8 jobs a second for 2 s: a whole number of cycles of 3 due times.
    assert 13 <= len(jobs) <= 19
    for r in jobs:
        assert r.submitted >= r.due and r.finished > r.submitted
        assert r.submitted - r.due < 0.25


def test_closed_loop_keeps_each_client_to_one_job(bench_copy):
    out = run_tiny(bench_copy, "tiny.closed2")
    events = sorted([(r.submitted, 1) for r in out.context.jobs]
                    + [(r.finished, -1) for r in out.context.jobs])
    in_flight = peak = 0
    for _, step in events:
        in_flight += step
        peak = max(peak, in_flight)
    assert peak == 2


def test_a_cell_with_no_limit_is_not_correct(bench_copy):
    (bench_copy / "chipbench" / "checks" / "tiny.closed2.json").unlink()
    out = run_tiny(bench_copy, "tiny.closed2", seconds=0.5)
    assert out.line["correct"] is False
    assert out.line["checks"]["widest_logit_gap"]["limit"] is None


def test_jobs_with_no_result_by_the_drain_count_as_failed(bench_copy,
                                                          monkeypatch):
    from chipbench import harness
    monkeypatch.setattr(harness, "DRAIN_S", 0.0)
    out = run_tiny(bench_copy, "tiny.closed2", seconds=0.5)
    assert out.line["failed"] >= 1
    assert out.line["correct"] is False
    assert out.line["checks"]["jobs_failed"]["value"] == out.line["failed"]
    given_up = [r for r in out.context.jobs if r.failed]
    assert all(r.gave_up is not None and r.finished is None
               for r in given_up)
