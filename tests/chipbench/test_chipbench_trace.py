"""The trace reduction: on a hand-made profile with known answers, and on
a small trace recorded on a v5e chip."""

import json
import lzma
from types import SimpleNamespace as NS

import pytest

from chipbench import trace
from chipbench.catalog import BENCH_DIR
from chipbench.harness import MODULES
from chipbench.spans import BLOCK, WARMUP, WINDOW_CLOSE, WINDOW_OPEN

RECORDED = BENCH_DIR / "data" / "trace-yi6b-closed4.xplane.pb.xz"


def _ev(name, start, end):
    return NS(name=name, start_ns=float(start), end_ns=float(end))


def _profile():
    """Window 100-200 ns. Device: a decode module 110-150 whose while op
    (110-150) holds a convert (115-135); a prefill module 160-180 with one
    fusion (160-180). Host: a warm-up span 100-112, a block 140-190."""
    host = NS(name="/host:CPU", lines=[
        NS(name="python3", events=[_ev(WINDOW_OPEN, 100, 100),
                                   _ev(WINDOW_CLOSE, 200, 200)]),
        NS(name="python3", events=[_ev(WARMUP, 100, 112),
                                   _ev(BLOCK, 140, 190)]),
    ])
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit__decode(7)", 110, 150),
                                       _ev("jit__prefill(9)", 160, 180),
                                       _ev("jit_other(3)", 60, 70)]),
        NS(name="XLA Ops", events=[
            _ev("%while.1 = (s32[], f32[8]) while(%t), body=%b", 110, 150),
            _ev("%convert.2 = bf16[8,16]{1,0} convert(f32[8,16] %p)",
                115, 135),
            _ev("%fusion.3 = bf16[8,4]{1,0} fusion(%a), kind=kLoop",
                160, 180),
            _ev("%copy.4 = f32[2] copy(%x)", 60, 70)]),
    ])
    return NS(planes=[host, device])


def test_reduction_of_a_hand_made_profile():
    r = trace.reduce(_profile(), MODULES, in_system=[(0.0, 0.9e-7)])
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(60e-9)
    assert r.module_n == {"jit__decode": 1, "jit__prefill": 1}
    assert r.module_s["jit__decode"] == pytest.approx(40e-9)
    assert r.module_mean_s("jit__prefill") == pytest.approx(20e-9)
    # Self times: the while less its convert; the ops outside the window
    # do not count.
    assert dict(r.ops) == pytest.approx({
        "jit__prefill: %fusion.3 fusion bf16[8,4]": 20e-9,
        "jit__decode: %while.1 while (...)": 20e-9,
        "jit__decode: %convert.2 convert bf16[8,16]": 20e-9})
    # Gaps: 100-110 under the warm-up, 150-160 under the block, 180-200
    # half under the block (a tie goes to the span), longest first.
    gaps = [(round(s * 1e9), label) for label, s in r.idle_gaps]
    assert sorted(gaps, reverse=True) == gaps == [
        (20, "block at 0.000s"), (10, "warmup at 0.000s"),
        (10, "block at 0.000s")]
    assert r.idle_by_label == pytest.approx(
        {"warmup": 10e-9, "block": 30e-9})
    # With the block gone, the last gap is "waiting" while a job is in the
    # system (until 185 ns) and "no job" once none is.
    p = _profile()
    p.planes[0].lines[1].events = [_ev(WARMUP, 100, 112)]
    r = trace.reduce(p, MODULES, in_system=[(0.0, 0.85e-7)])
    assert r.idle_by_label == pytest.approx(
        {"warmup": 10e-9, "waiting": 10e-9, "no job": 20e-9})
    with pytest.raises(trace.TraceError):
        trace.reduce(_profile(), ("jit__none",)).module_mean_s("jit__none")


def test_a_trace_without_markers_or_devices_is_refused():
    p = _profile()
    p.planes[0].lines[0].events = []
    with pytest.raises(trace.TraceError, match="markers"):
        trace.reduce(p, MODULES)
    p = _profile()
    p.planes = p.planes[:1]
    with pytest.raises(trace.TraceError, match="TPU plane"):
        trace.reduce(p, MODULES)


def test_short_op_names():
    assert trace.short_op_name(
        "%convert.19 = bf16[8,4096,11008]{2,1,0:T(8,128)(2,1)} "
        "convert(f32[8,4096,11008]{2,1,0} %w)") \
        == "%convert.19 convert bf16[8,4096,11008]"
    assert trace.short_op_name(
        "%while.1 = (s32[]{:T(128)}, bf16[8,4096]{1,0}) while((s32[]) %t)")\
        == "%while.1 while (...)"


def test_reduction_of_a_trace_recorded_on_the_chip():
    """1.5 s of yi-6b-8L under mixed_closed4 on one v5e chip (seed 201),
    traced by ``run.py --trace 1 --keep-trace``, with what the benchmark's
    own records counted in the window."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_serialized_xspace(
        lzma.decompress(RECORDED.read_bytes()))
    records = json.loads(RECORDED.with_name(
        "trace-yi6b-closed4.json").read_text())
    r = trace.reduce(profile, MODULES)
    assert r.window_s == pytest.approx(records["window_s"], abs=0.01)
    assert 0.9 * r.window_s < r.busy_s < r.window_s
    assert sum(r.idle_by_label.values()) == pytest.approx(
        r.window_s - r.busy_s)
    # Every prefill that started in the window ran in it; a block that
    # started near the close runs its last decode steps after it.
    assert r.module_n["jit__prefill"] == records["prefills"] == 8
    assert records["decode_steps"] - 8 <= r.module_n["jit__decode"] \
        <= records["decode_steps"]
    assert r.module_mean_s("jit__decode") == pytest.approx(0.0207, rel=0.05)
    assert r.module_mean_s("jit__prefill") == pytest.approx(0.0899, rel=0.05)
    assert len(r.ops) == trace.TOP and r.ops[0][0].startswith("jit__prefill")
    assert [s for _, s in r.ops] == sorted((s for _, s in r.ops),
                                           reverse=True)
    assert any("convert bf16[8,4096,11008]" in name for name, _ in r.ops)
