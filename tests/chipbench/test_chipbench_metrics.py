"""Metric readers on hand-made spans, with hand-worked values."""

import json

import pytest

from chipbench import catalog, harness
from chipbench.spans import JobRecord, Recorder, Span
from chipbench.trace import Reduction, TraceError

TRAFFIC = {"batch": 8, "prompt_len": 512,
           "classes": [{"blocks": 2, "per_cycle": 1}],
           "arrivals": {"kind": "closed", "clients": 1}}


def _ctx(trace=None):
    cfg = json.loads(
        (catalog.BENCH_DIR / "configs" / "yi-6b-8L.json").read_text())
    cell = catalog.Cell(name="t", chips=1, config=cfg, traffic=TRAFFIC,
                        checks=None)
    rec = Recorder()
    # Job 0: due at 10, submitted 10, warm-up 10.0-10.1, blocks 10.3-10.5
    # (its prefill and 8 decode steps) and 11.0-11.2, result at 11.25.
    rec.warmups.append(Span(0, 10.0, 10.1, decode_steps=1))
    rec.blocks.append(Span(0, 10.3, 10.5, tokens=72, prefill=True,
                           decode_steps=8, decode_start=0))
    rec.blocks.append(Span(0, 11.0, 11.2, tokens=64, decode_steps=8,
                           decode_start=8))
    jobs = [JobRecord(0, 2, 1, due=10.0, submitted=10.0, finished=11.25)]
    return harness.Context(cell=cell, seconds=2.0, t0=10.0, t1=12.0,
                           setup_s=3.0, jobs=jobs, recorder=rec,
                           device_kind="TPU v5 lite", trace=trace)


def _read(name, ctx):
    return catalog.load_module("metrics", name).read(ctx)


def test_host_clock_readers():
    ctx = _ctx()
    assert _read("tokens_per_s", ctx) == pytest.approx(136 / 2.0)
    assert _read("turnaround_p90_s", ctx) == pytest.approx(1.25)
    assert _read("antt", ctx) == pytest.approx(1.25 / 0.4)
    assert _read("setup_s", ctx) == 3.0
    assert _read("arrival_warmup_s", ctx) == pytest.approx(0.1)
    assert _read("queue_wait_s", ctx) == pytest.approx(0.2)
    assert _read("decode_block_s", ctx) == pytest.approx(0.2)
    # In the system 10.0-11.25; spans cover 0.5 s of it.
    assert _read("host_gap_share", ctx) == pytest.approx(100 * 0.75 / 2.0)
    assert _read("peak_hbm_gib", ctx) is None
    for name in ("decode_roofline", "prefill_roofline", "mfu",
                 "device_idle_share"):
        assert _read(name, ctx) is None


def test_device_readers():
    trace = Reduction(window_s=2.0, busy_s=1.5,
                      module_s={"jit__decode": 17 * 0.02,
                                "jit__prefill": 2 * 0.1},
                      module_n={"jit__decode": 17, "jit__prefill": 2})
    ctx = _ctx(trace)
    assert _read("device_idle_share", ctx) == pytest.approx(25.0)
    counts = ctx.cell.counts()
    cfg = ctx.cell.config
    flops, nbytes = counts.prefill(cfg, batch=8, prompt_len=512)
    assert _read("prefill_roofline", ctx) == pytest.approx(
        100 * max(flops / 197e12, nbytes / 819e9) / 0.1)
    roof = [max(f / 197e12, b / 819e9) for f, b in (
        counts.decode_step(cfg, batch=8, filled=512 + i)
        for i in list(range(16)) + [0])]
    assert _read("decode_roofline", ctx) == pytest.approx(
        100 * sum(roof) / 17 / 0.02)
    work = flops + sum(counts.decode_step(cfg, batch=8, filled=512 + i)[0]
                       for i in range(16))
    assert _read("mfu", ctx) == pytest.approx(100 * work / (2.0 * 197e12))


def test_a_module_that_never_ran_fails_loudly():
    trace = Reduction(window_s=2.0, busy_s=1.5,
                      module_s={"jit__decode": 0.0, "jit__prefill": 0.2},
                      module_n={"jit__decode": 0, "jit__prefill": 2})
    with pytest.raises(TraceError):
        _read("decode_roofline", _ctx(trace))


def test_unknown_device_kind_has_no_peaks():
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.load_peaks("cpu")
