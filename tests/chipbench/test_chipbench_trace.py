"""The trace reduction: on a hand-made profile with known answers, and on
a small trace recorded on a v5e chip."""

import json
import lzma
import sys
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


# ------------------------------------------------------------ by scope
#: What the reduction of the recorded trace read before it could read
#: device time by scope: every field that a metric reads today.
RECORDED_BUSY_S = 1.4801628
RECORDED_MODULE_S = {"jit__decode": 0.766763949, "jit__prefill": 0.71887853}
RECORDED_OPS = [
    ("jit__prefill: %fusion.137 fusion bf16[8,512,11008]",
     0.13339146300000002),
    ("jit__prefill: %fusion.139 fusion bf16[8,512,4096]", 0.128546586),
    ("jit__prefill: %fusion.138 fusion bf16[8,512,11008]",
     0.12584062299999998),
    ("jit__decode: %convert.19 convert bf16[8,4096,11008]",
     0.12046318499999997),
    ("jit__decode: %convert.18 convert bf16[8,4096,11008]",
     0.12043842999999999),
    ("jit__decode: %convert.17 convert bf16[8,11008,4096]",
     0.11877187099999999),
    ("jit__decode: %convert_element_type.46 convert bf16[64000,4096]",
     0.08792309399999997),
    ("jit__decode: %iota_reduce_fusion fusion (...)", 0.04992479899999999),
    ("jit__prefill: %fusion.136 fusion (...)", 0.047999456),
    ("jit__prefill: %fusion.129 fusion f32[8,512,32,128]", 0.047761167)]
RECORDED_IDLE_GAPS = [
    ("warmup at 0.713s", 0.002992867), ("block at 1.493s", 0.002211034),
    ("warmup at 0.115s", 0.002069488), ("warmup at 0.488s", 0.002022758),
    ("block at 1.233s", 0.002021964), ("block at 0.375s", 0.001815882),
    ("block at 0.974s", 0.00179534), ("warmup at 0.600s", 0.001589591),
    ("no job at 0.002s", 0.001508056), ("no job at 0.003s", 0.000920857)]


def _recorded():
    from jax.profiler import ProfileData

    raw = lzma.decompress(RECORDED.read_bytes())
    return trace.reduce(ProfileData.from_serialized_xspace(raw), MODULES,
                        xspace=raw), raw


def _no_proto_import(monkeypatch):
    def refuse(name):
        raise AssertionError(f"{name} imported by a reduction that read no "
                             f"scope")
    monkeypatch.setattr(trace, "_proto_module", refuse)


def test_the_recorded_reduction_reads_as_before_and_parses_no_proto(
        monkeypatch):
    _no_proto_import(monkeypatch)
    r, _ = _recorded()
    assert r.busy_s == RECORDED_BUSY_S
    assert r.module_s == RECORDED_MODULE_S
    assert r.ops == RECORDED_OPS
    assert r.idle_gaps == RECORDED_IDLE_GAPS
    assert r.breakdown() == {
        "device_ops": [[n, s] for n, s in RECORDED_OPS],
        "idle_gaps": [[n, s] for n, s in RECORDED_IDLE_GAPS]}
    assert not r.scopes_read


def _need_proto_modules():
    """Reading a scope parses the trace's protos with tensorflow's
    modules, which no requirement of the project brings."""
    for name in (trace.XPLANE_PB2, trace.HLO_PB2):
        pytest.importorskip(name)


def test_device_time_by_scope_on_the_recorded_trace():
    """The layer scan's body (``while``), the time outside it and the
    time that no instruction answers make up the module's device time."""
    _need_proto_modules()
    r, raw = _recorded()
    names = trace.hlo_op_names(raw, {run for run, _ in
                                     r.instructions["jit__decode"]})
    n = r.module_n["jit__decode"]
    outside = sum(t for (run, inst), t in r.instructions["jit__decode"]
                  .items() if inst in names.get(run, {})
                  and "while" not in names[run][inst].split("/")) / n
    inside = r.scope_s("jit__decode", "while")
    missing = r.unattributed_s["jit__decode"]
    own = sum(r.instructions["jit__decode"].values()) / n
    assert inside + outside + missing == pytest.approx(own, rel=1e-9)
    assert inside + outside + missing == pytest.approx(
        r.module_mean_s("jit__decode"), rel=0.01)
    assert inside == pytest.approx(0.004386333810810811, rel=1e-9)
    assert 0 <= missing < 0.01 * r.module_mean_s("jit__decode")
    assert r.scope_s("jit__decode", "no_such_scope") == 0.0
    # A segment, not a substring: "whil" names no scope.
    assert r.scope_s("jit__decode", "whil") == 0.0
    assert r.scopes_read
    # Under 1% unattributed, the breakdown names nothing more.
    assert r.breakdown()["device_ops"] == [[k, s] for k, s in RECORDED_OPS]


def _xspace(protos):
    """A serialized trace holding only a metadata plane with an
    ``Hlo Proto`` for each run: ``{run: {instruction: op_name}}``."""
    from tensorflow.compiler.xla.service import hlo_pb2
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    plane = space.planes.add(name=trace.METADATA_PLANE)
    plane.stat_metadata[1].id = 1
    plane.stat_metadata[1].name = trace.HLO_PROTO
    for k, (run, ops) in enumerate(protos.items(), start=10):
        proto = hlo_pb2.HloProto()
        comp = proto.hlo_module.computations.add(name="main")
        for inst, op_name in ops.items():
            comp.instructions.add(name=inst).metadata.op_name = op_name
        meta = plane.event_metadata[k]
        meta.id, meta.name = k, run
        meta.stats.add(metadata_id=1,
                       bytes_value=proto.SerializeToString())
    return space.SerializeToString()


def test_device_time_by_scope_of_a_hand_made_profile():
    """The decode run's while (20 ns of its own) and the convert in its
    body (20 ns) carry scope paths; the prefill run has no proto."""
    _need_proto_modules()
    raw = _xspace({"jit__decode(7)": {
        "while.1": "jit(_decode)/while",
        "convert.2": "jit(_decode)/while/body/convert_element_type"}})
    r = trace.reduce(_profile(), MODULES, xspace=raw)
    plain = r.breakdown()
    assert r.scope_s("jit__decode", "while") == pytest.approx(40e-9)
    assert r.scope_s("jit__decode", "body") == pytest.approx(20e-9)
    assert r.scope_s("jit__decode", "jit(_decode)") == pytest.approx(40e-9)
    assert r.scope_s("jit__prefill", "while") == 0.0
    assert r.unattributed_s == pytest.approx(
        {"jit__decode": 0.0, "jit__prefill": 20e-9})
    # Once a scope was read, the prefill's unattributed time is named.
    ops = dict(map(tuple, r.breakdown()["device_ops"]))
    assert ops.pop("jit__prefill: no HLO instruction") == pytest.approx(
        20e-9)
    assert ops == dict(map(tuple, plain["device_ops"]))
    with pytest.raises(trace.TraceError, match="never ran"):
        trace.reduce(_profile(), ("jit__none",), xspace=raw).scope_s(
            "jit__none", "while")


def test_a_scope_without_the_trace_or_its_proto_modules_is_refused(
        monkeypatch):
    with pytest.raises(trace.TraceError, match="no serialized trace"):
        trace.reduce(_profile(), MODULES).scope_s("jit__decode", "while")
    monkeypatch.setitem(sys.modules, trace.XPLANE_PB2, None)
    r = trace.reduce(_profile(), MODULES, xspace=b"")
    with pytest.raises(trace.TraceError, match=trace.XPLANE_PB2):
        r.scope_s("jit__decode", "while")
