"""Closed-loop scenario tests: the two-tier contract, the completion->
arrival feedback edge on both machines, process determinism (in- and
cross-process), admission semantics, sweep cache round-trips and the
executor solo-baseline pool-fidelity keying."""

import subprocess
import sys
from pathlib import Path

import pytest

from repro.core.events import ArrivalSource
from repro.core.executor import ExecutorJob, LaneExecutor
from repro.core.policies import make_policy
from repro.core.scenarios import (
    ClosedLoopScenario,
    MGkClosed,
    SCENARIOS,
    ThinkTime,
    make_scenario,
    open_loop_names,
)
from repro.core.simulator import Simulator, simulate
from repro.core.sweep import (
    SweepSpec,
    _executor_solo_key,
    run_sweep,
)
from repro.core.workload import Arrival, ERCBENCH, scaled_spec

#: Tiny kernels: real ERCBench structure, two orders of magnitude cheaper.
TINY = {
    "JPEG-d": scaled_spec(ERCBENCH["JPEG-d"], num_blocks=48, mean_t=900.0),
    "SAD": scaled_spec(ERCBENCH["SAD"], num_blocks=64, mean_t=1500.0),
    "AES-e": scaled_spec(ERCBENCH["AES-e"], num_blocks=30, mean_t=700.0),
}

#: Reduced grids for executor cells (every block really executes).
TINYX = {
    "SAD": scaled_spec(ERCBENCH["SAD"], num_blocks=10, mean_t=1500.0),
    "JPEG-d": scaled_spec(ERCBENCH["JPEG-d"], num_blocks=8, mean_t=900.0),
}


def mgk(seed=0, **kw):
    kw.setdefault("names", tuple(TINY))
    kw.setdefault("specs", TINY)
    kw.setdefault("n_total", 8)
    kw.setdefault("mean_interarrival", 3_000.0)
    kw.setdefault("population", 3)
    return MGkClosed(seed=seed, **kw)


def think(seed=0, **kw):
    kw.setdefault("names", tuple(TINY))
    kw.setdefault("specs", TINY)
    kw.setdefault("n_tenants", 2)
    kw.setdefault("mean_think", 2_000.0)
    kw.setdefault("n_rounds", 3)
    return ThinkTime(seed=seed, **kw)


# ------------------------------------------------------------------ contract
def test_registry_contains_the_closed_loop_scenarios():
    assert {"mgk-closed", "think-time", "diurnal"} <= set(SCENARIOS)
    assert issubclass(SCENARIOS["mgk-closed"], ClosedLoopScenario)
    assert issubclass(SCENARIOS["think-time"], ClosedLoopScenario)
    assert not issubclass(SCENARIOS["diurnal"], ClosedLoopScenario)


def test_open_loop_names_excludes_the_closed_tier():
    names = open_loop_names()
    assert "poisson-open" in names and "diurnal" in names
    assert "mgk-closed" not in names and "think-time" not in names


def test_closed_loop_workloads_raises_with_guidance():
    with pytest.raises(TypeError, match="completion-driven"):
        mgk().workloads()


def test_make_scenario_resolves_closed_loop_names():
    scn = make_scenario("mgk-closed", seed=2, names=tuple(TINY), specs=TINY)
    assert isinstance(scn, MGkClosed) and scn.seed == 2
    re = scn.reseeded(5)
    assert re.seed == 5 and scn.seed == 2


def test_process_params_cover_draw_determining_fields():
    a = mgk().process_params()
    assert a["scenario"] == "mgk-closed"
    assert a["params"]["population"] == 3
    assert set(a["specs"]) == set(TINY)
    b = mgk(mean_interarrival=9_999.0).process_params()
    assert a != b                      # offered load is part of the params
    assert mgk(seed=3).process_params() == a   # ...but the seed is not


def test_unknown_process_name_rejected():
    with pytest.raises(ValueError, match="unknown workload"):
        mgk().make_process("nope")


# ------------------------------------------------------------- determinism
def drive(process, service_time=2_500.0):
    """Drive a process with a deterministic completion script (no machine):
    always complete the oldest in-flight arrival ``service_time`` after
    max(its arrival, previous completion)."""
    emitted = list(process.initial())
    in_flight = list(emitted)
    clock = 0.0
    log = []
    while in_flight:
        a = in_flight.pop(0)
        clock = max(clock, a.time) + service_time
        fresh = process.on_completion(a.key, clock)
        log.append((a.key, clock, tuple((f.key, f.time) for f in fresh)))
        emitted += fresh
        in_flight += fresh
    return [(a.key, a.spec.name, a.time) for a in emitted], log


@pytest.mark.parametrize("factory", [mgk, think])
def test_same_params_and_seed_reproduce_identical_sequences(factory):
    scn = factory(seed=7)
    name = scn.process_names()[0]
    seq_a, log_a = drive(scn.make_process(name))
    seq_b, log_b = drive(factory(seed=7).make_process(name))
    assert seq_a == seq_b and log_a == log_b
    seq_c, _ = drive(factory(seed=8).make_process(name))
    assert seq_a != seq_c


_SEQ_SNIPPET = """
import sys
sys.path.insert(0, {testdir!r})
from test_closedloop import drive, mgk, think
for factory in (mgk, think):
    scn = factory(seed=int(sys.argv[1]))
    seq, _ = drive(scn.make_process(scn.process_names()[0]))
    print(repr(seq))
"""


def test_sequences_identical_across_processes():
    # Fresh interpreter => fresh hash salt, fresh numpy state: the
    # completion-driven arrival sequence must still be bit-identical
    # (process RNG streams are crc32-derived, not hash()).
    here = []
    for factory in (mgk, think):
        scn = factory(seed=5)
        seq, _ = drive(scn.make_process(scn.process_names()[0]))
        here.append(repr(seq))
    testdir = str(Path(__file__).resolve().parent)
    src = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run(
        [sys.executable, "-c", _SEQ_SNIPPET.format(testdir=testdir), "5"],
        capture_output=True, text=True, check=True,
        env={"PYTHONPATH": src, "PATH": "/usr/bin:/bin"},
    )
    assert out.stdout.splitlines() == here


def test_des_closed_loop_run_is_deterministic():
    scn = mgk(seed=0)
    name = scn.process_names()[0]

    def once():
        return simulate([], lambda: make_policy("srtf"), seed=0,
                        arrival_source=scn.make_process(name))

    a, b = once(), once()
    assert a.turnaround == b.turnaround and a.finish == b.finish
    assert a.arrival == b.arrival


# --------------------------------------------------------- feedback edge
def test_completions_drive_arrivals_through_the_des():
    scn = think(seed=1)
    res = simulate([], lambda: make_policy("fifo"), seed=0,
                   arrival_source=scn.make_process("think.0"))
    # every tenant completed every round
    assert len(res.turnaround) == 2 * 3
    # rounds 2+ arrive strictly after some earlier completion (the think
    # time is Exp-distributed > 0 with probability 1)
    first_round = sorted(res.arrival.values())[:2]
    completions = sorted(res.finish.values())
    for key, t in res.arrival.items():
        if t in first_round:
            continue
        assert any(c < t for c in completions), (key, t)


def test_mgk_population_bound_holds_in_the_des():
    scn = mgk(seed=3, population=2, n_total=10)
    res = simulate([], lambda: make_policy("fifo"), seed=0,
                   arrival_source=scn.make_process("mgk.0"))
    assert len(res.turnaround) == 10
    # At equal timestamps the completion precedes the arrival it released
    # (the feedback edge fires on completion), so sort -1 before +1.
    events = sorted([(t, +1) for t in res.arrival.values()]
                    + [(t, -1) for t in res.finish.values()])
    in_system = peak = 0
    for _, delta in events:
        in_system += delta
        peak = max(peak, in_system)
    assert peak <= 2


def test_mgk_admission_drop_rejects_when_full():
    # One kernel in the system at a time and offered arrivals far faster
    # than completions: the loss variant must drop some of them.
    scn = mgk(seed=0, population=1, n_total=10, mean_interarrival=100.0,
              admission="drop")
    proc = scn.make_process("mgk.0")
    sim = Simulator([], make_policy("fifo"), seed=0)
    sim.attach_arrival_source(proc)
    res = sim.run()
    assert proc.dropped > 0
    assert len(res.turnaround) + proc.dropped == 10
    with pytest.raises(ValueError, match="admission"):
        mgk(admission="reject")


def test_mgk_ignores_completions_of_foreign_kernels():
    # The machine reports EVERY natural completion; static arrivals mixed
    # with an attached source must not corrupt the population accounting
    # (pre-fix, each foreign completion decremented in_system and let the
    # process release population+1 concurrent kernels).
    scn = mgk(seed=1, population=1, n_total=4)
    proc = scn.make_process("mgk.0")
    static = [Arrival(TINY["AES-e"], 0.0, uid="static#0"),
              Arrival(TINY["AES-e"], 10.0, uid="static#1")]
    res = simulate(static, lambda: make_policy("fifo"), seed=0,
                   arrival_source=proc)
    assert len(res.turnaround) == 4 + 2
    own = {k: t for k, t in res.arrival.items() if not k.startswith("static")}
    events = sorted([(t, +1) for t in own.values()]
                    + [(res.finish[k], -1) for k in own])
    in_system = peak = 0
    for _, delta in events:
        in_system += delta
        peak = max(peak, in_system)
    assert peak <= 1
    assert proc._in_system == 0          # every own completion accounted


def test_injected_arrivals_never_land_in_the_past():
    scn = think(seed=2)
    res = simulate([], lambda: make_policy("fifo"), seed=0,
                   arrival_source=scn.make_process("think.0"))
    for key, t_in in res.arrival.items():
        assert res.finish[key] >= t_in


def test_duplicate_injection_and_double_attach_rejected():
    sim = Simulator([Arrival(TINY["JPEG-d"], 0.0, uid="J#0")],
                    make_policy("fifo"))
    with pytest.raises(ValueError, match="duplicate"):
        sim.inject_arrival(Arrival(TINY["SAD"], 0.0, uid="J#0"))

    class Empty:
        def initial(self):
            return []

        def on_completion(self, key, now):
            return []

    sim.attach_arrival_source(Empty())
    with pytest.raises(ValueError, match="already attached"):
        sim.attach_arrival_source(Empty())


class _RecordingSource:
    """ArrivalSource that logs completions and emits nothing."""

    def __init__(self, first):
        self._first = list(first)
        self.completions = []

    def initial(self):
        return self._first

    def on_completion(self, key, now):
        self.completions.append(key)
        return []


def test_recording_source_satisfies_the_protocol():
    assert isinstance(_RecordingSource([]), ArrivalSource)


def test_executor_cancellation_does_not_feed_the_loop():
    def bridge(arrival):
        return ExecutorJob(
            name=arrival.spec.name, num_blocks=4, max_residency=2,
            make_block_fn=lambda residency: (lambda: None),
            arrival=arrival.time)

    src = _RecordingSource([Arrival(TINYX["SAD"], 0.0, uid="SAD#0"),
                            Arrival(TINYX["JPEG-d"], 0.0, uid="JPEG-d#1")])
    ex = LaneExecutor([], make_policy("fifo"), n_lanes=2, job_bridge=bridge)
    ex.attach_arrival_source(src)
    ex.cancel("JPEG-d#1")
    ex.run()
    # the cancelled job posted KernelEnded (policy bookkeeping) but must
    # not have fed the closed loop; the natural completion did.
    assert src.completions == ["SAD#0"]


def test_executor_inject_requires_a_bridge():
    ex = LaneExecutor([], make_policy("fifo"), n_lanes=2)
    with pytest.raises(ValueError, match="job_bridge"):
        ex.inject_arrival(Arrival(TINYX["SAD"], 0.0, uid="SAD#0"))


# ------------------------------------------------------------------- sweep
def closed_spec(policies, **kw):
    return SweepSpec(scenarios=(mgk(),), policies=tuple(policies), **kw)


def test_closed_loop_sweep_roundtrips_the_cache(tmp_path):
    spec = closed_spec(("fifo", "srtf", "srtf-adaptive"), seeds=(0, 1))
    cold = run_sweep(spec, cache_dir=tmp_path)
    assert cold.stats["computed"] == 6 and cold.stats["cache_hits"] == 0
    warm = run_sweep(spec, cache_dir=tmp_path)
    assert warm.stats["computed"] == 0 and warm.stats["cache_hits"] == 6
    for a, b in zip(cold.cells, warm.cells):
        assert a == b                  # dataclass equality: every float
    # the warm cell's arrival map survived the JSON round-trip exactly,
    # so queueing metrics are computable from cache alone
    q = warm.cells[0].queueing(warmup_frac=0.1)
    assert q.mean_response > 0.0 and q.n_completed > 0


def test_closed_loop_cache_key_covers_process_params(tmp_path):
    run_sweep(closed_spec(("fifo",)), cache_dir=tmp_path)
    # same scenario, different offered load => different process params
    # => a fresh cell
    other = SweepSpec(scenarios=(mgk(mean_interarrival=9_999.0),),
                      policies=("fifo",))
    r = run_sweep(other, cache_dir=tmp_path)
    assert r.stats["computed"] == 1


def test_closed_loop_multiprocess_equals_serial():
    spec = closed_spec(("fifo", "srtf"), seeds=(0, 1))
    assert run_sweep(spec, jobs=2).cells == run_sweep(spec, jobs=1).cells


def test_closed_loop_rejects_oracle_order_policies():
    with pytest.raises(ValueError, match="oracle-reordered"):
        run_sweep(closed_spec(("sjf",)))
    with pytest.raises(ValueError, match="oracle-reordered"):
        run_sweep(closed_spec(("ljf",)))


def test_closed_loop_truncation_first_class():
    cell, = run_sweep(closed_spec(("fifo",), until=4_000.0)).cells
    assert cell.unfinished
    assert cell.window.end_time <= 4_000.0
    assert cell.arrival                      # in-flight arrivals recorded


def test_closed_loop_executor_cells_share_the_record_shape(tmp_path):
    scn = MGkClosed(seed=0, names=tuple(TINYX), specs=TINYX, n_total=4,
                    mean_interarrival=2_000.0, population=2)
    spec = SweepSpec(scenarios=(scn,), policies=("fifo", "srtf"),
                     machine="executor", n_sm=3)
    result = run_sweep(spec, cache_dir=tmp_path)
    assert result.stats["machine"] == "executor"
    for cell in result.cells:
        assert cell.measured
        assert cell.window.n_finished == 4 and not cell.unfinished
        assert set(cell.arrival) == set(cell.turnaround)
        assert cell.metrics is not None and cell.metrics.stp > 0.0
        q = cell.queueing(warmup_frac=0.0)
        assert q.mean_response > 0.0
    # executor closed-loop cells are measurements: nonce-keyed, re-measured
    r2 = run_sweep(spec, cache_dir=tmp_path)
    assert r2.stats["cache_hits"] == 0 and r2.stats["computed"] == 2
    # ...while the mix's solo baselines came from the cache
    assert r2.stats["solo_computed"] == 0


# ------------------------------------------- executor solo pool fidelity
def test_executor_solo_key_folds_in_pool_width():
    spec = TINYX["SAD"]
    assert _executor_solo_key(spec, 3, 1) != _executor_solo_key(spec, 3, 2)
    assert _executor_solo_key(spec, 3, 2) == _executor_solo_key(spec, 3, 2)


@pytest.mark.slow
def test_executor_parallel_sweep_measures_solos_in_the_pool(tmp_path,
                                                          monkeypatch):
    from repro.core.scenarios import TraceReplay

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")   # spawned workers inherit it
    scn = TraceReplay(trace=[{"kernel": "SAD", "time": 0.0},
                             {"kernel": "JPEG-d", "time": 100.0}],
                      specs=TINYX, name="xtiny")
    spec = SweepSpec(scenarios=(scn,), policies=("fifo", "srtf"),
                     machine="executor", n_sm=3)
    cold = run_sweep(spec, jobs=2, cache_dir=tmp_path)
    assert cold.stats["solo_pool_jobs"] == 2
    assert cold.stats["solo_computed"] == 2
    # the pool-measured baselines are cached under the pool-width key and
    # reused by the next same-width run...
    warm = run_sweep(spec, jobs=2, cache_dir=tmp_path)
    assert warm.stats["solo_computed"] == 0
    # ...but a serial run must NOT reuse them (different contention
    # conditions => different key)
    serial = run_sweep(spec, jobs=1, cache_dir=tmp_path)
    assert serial.stats["solo_pool_jobs"] == 1
    assert serial.stats["solo_computed"] == 2
