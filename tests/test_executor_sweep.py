"""Executor-machine sweeps: the scenario->ExecutorJob bridge, record-shape
parity with DES cells, measured-cell nonce semantics, cached executor solo
runtimes, and the quarantine-starvation regression."""

import math

import pytest

from repro.core.executor import ExecutorJob, LaneExecutor
from repro.core.policies import make_policy
from repro.core.scenarios import TraceReplay, executor_workload
from repro.core.sweep import SweepSpec, run_sweep
from repro.core.workload import Arrival, ERCBENCH, scaled_spec

#: Reduced grids (every block is a real jitted execution — keep them tiny).
TINYX = {
    "SAD": scaled_spec(ERCBENCH["SAD"], num_blocks=12, mean_t=1500.0),
    "JPEG-d": scaled_spec(ERCBENCH["JPEG-d"], num_blocks=8, mean_t=900.0),
}

TRACE = [
    {"kernel": "SAD", "time": 0.0},
    {"kernel": "JPEG-d", "time": 100.0},
]


def exec_spec(policies, **kw):
    scn = TraceReplay(trace=TRACE, specs=TINYX, name="xtiny")
    return SweepSpec(scenarios=(scn,), policies=tuple(policies),
                     machine="executor", n_sm=3, **kw)


# ------------------------------------------------------------------ bridge
def test_bridge_preserves_uids_times_and_grid():
    arrivals = [Arrival(TINYX["SAD"], 0.0, uid="SAD#0"),
                Arrival(TINYX["JPEG-d"], 50.0, uid="JPEG-d#1")]
    pairs = executor_workload(arrivals, n_lanes=3, time_scale=1e-5)
    assert [k for k, _ in pairs] == ["SAD#0", "JPEG-d#1"]
    job = pairs[1][1]
    assert job.name == "JPEG-d"
    assert job.num_blocks == TINYX["JPEG-d"].num_blocks
    assert job.max_residency == min(TINYX["JPEG-d"].max_residency, 3)
    assert job.arrival == pytest.approx(50.0 * 1e-5)


def test_unknown_machine_rejected():
    with pytest.raises(ValueError, match="unknown machine"):
        SweepSpec(scenarios=("pair-stagger",), policies=("fifo",),
                  machine="quantum")


# ------------------------------------------------------------- sweep cells
def test_executor_cells_share_des_record_shape():
    result = run_sweep(exec_spec(("fifo", "srtf")))
    assert result.stats["machine"] == "executor"
    assert len(result.cells) == 2
    for cell in result.cells:
        assert cell.measured
        # Scenario uids survive the bridge into the cell's kernel keys.
        assert set(cell.turnaround) == {"SAD#0", "JPEG-d#1"}
        assert cell.names["SAD#0"] == "SAD"
        assert cell.window.n_finished == 2 and not cell.unfinished
        assert cell.window.makespan > 0.0
        assert 0.0 <= cell.window.utilization <= 1.0 + 1e-9
        assert cell.metrics is not None and cell.metrics.stp > 0.0
    # The label-free record shape feeds the same rendering code as DES.
    assert result.summary(policy="fifo").antt > 0.0


def test_executor_cells_are_nonce_keyed_solo_is_not(tmp_path, monkeypatch):
    spec = exec_spec(("fifo",))
    r1 = run_sweep(spec, cache_dir=tmp_path)
    assert r1.stats["computed"] == 1

    # Second run: solo baselines must come from the cache...
    import repro.core.sweep as sweep_mod

    def boom(*a, **k):
        raise AssertionError("executor solo re-measured despite warm cache")

    monkeypatch.setattr(sweep_mod, "solo_runtime_executor", boom)
    r2 = run_sweep(spec, cache_dir=tmp_path)
    # ...while cells re-measure every run (per-run nonce: wall-time is not
    # bit-reproducible, so a cross-run cache hit would be a lie).
    assert r2.stats["cache_hits"] == 0
    assert r2.stats["computed"] == 1


def test_executor_truncation_first_class():
    cell, = run_sweep(exec_spec(("fifo",), until=1e-9)).cells
    assert cell.window.n_finished == 0
    assert math.isnan(cell.window.stp)
    assert set(cell.unfinished) == {"SAD#0", "JPEG-d#1"}
    assert cell.metrics is None


@pytest.mark.slow
def test_executor_parallel_fanout_produces_all_cells(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")   # spawned workers inherit it
    result = run_sweep(exec_spec(("fifo", "srtf", "mpmax")), jobs=2,
                       cache_dir=tmp_path)
    assert result.stats["computed"] == 3
    assert all(c.metrics is not None for c in result.cells)


@pytest.mark.parametrize("platforms", [None, "tpu", "cpu,tpu"])
def test_executor_fanout_refused_off_the_cpu(tmp_path, monkeypatch,
                                             platforms):
    """One chip belongs to one process: executor cells are never handed to
    spawned workers unless JAX is held to the CPU."""
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    with pytest.raises(ValueError, match="JAX_PLATFORMS=cpu"):
        run_sweep(exec_spec(("fifo",)), jobs=2, cache_dir=tmp_path)


# ----------------------------------------------------- quarantine regression
def _noop_job(name="j", blocks=6):
    return ExecutorJob(name=name, num_blocks=blocks, max_residency=3,
                       make_block_fn=lambda residency: (lambda: None))


def test_quarantine_never_empties_the_machine():
    """Regression: stale EWMAs of already-quarantined lanes dragged the
    median down across calls until every lane was marked failed; pending
    jobs then starved with a drained event queue (the async service awaits
    forever).  The EWMA walk below previously quarantined all three lanes;
    with the median over in-service lanes only, the cascade stops after
    the genuine straggler (and a floor keeps >= 1 lane regardless)."""
    ex = LaneExecutor([_noop_job()], make_policy("fifo"), n_lanes=3)
    ex.lane_t_ewma = {0: 1.0, 1: 100.0, 2: 10.0}
    ex._maybe_quarantine()            # lane 1 diverges -> quarantined
    ex.lane_t_ewma[0] = 1000.0
    ex._maybe_quarantine()            # pre-fix: stale median kills lane 0
    ex.lane_t_ewma[2] = 10_000.0
    ex._maybe_quarantine()            # pre-fix: ...and then the LAST lane
    assert sum(1 for lane in ex.sms if not lane.failed) >= 2
    results = ex.run()
    assert results["j#0"].blocks == 6     # the job still completes


def test_quarantine_still_removes_stragglers():
    ex = LaneExecutor([_noop_job()], make_policy("fifo"), n_lanes=4)
    ex.lane_t_ewma = {0: 1.0, 1: 1.0, 2: 1.0, 3: 50.0}
    ex._maybe_quarantine()
    assert ex.sms[3].failed
    assert sum(1 for lane in ex.sms if not lane.failed) == 3
