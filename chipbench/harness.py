"""The body of one run: set-up, the measured window, the output check, and
the result line. ``run.py`` is the command; it checks for the chip first.

The window drives the served scheduling path as a client would: jobs of
``repro.core.jobs.make_serve_job`` go through
``SchedulerService.submit`` and ``await handle.result()``, and every time is
taken on the host's clock (``time.perf_counter``).
"""

from __future__ import annotations

import asyncio
import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic as tr
from chipbench.catalog import BENCH_DIR, Cell
from chipbench.spans import (
    WINDOW_CLOSE,
    WINDOW_OPEN,
    CompileCounter,
    JobRecord,
    Recorder,
)

#: Seconds that jobs still running when the window closes may take to end.
DRAIN_S = 60.0
#: Jobs of the window whose tokens the output check compares.
SAMPLE_JOBS = 3
#: Program modules whose device time the trace reduction reads.
MODULES = ("jit__decode", "jit__prefill")

#: Keys of a configuration file and the program's ``ArchConfig`` fields.
CONFIG_FIELDS = {
    "vocab_size": "vocab_size",
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "head_dim": "head_dim",
    "rope_theta": "rope_theta",
}


def log(*parts) -> None:
    print("[chipbench]", *parts, file=sys.stderr, flush=True)


def program_config(cfg: Dict):
    """The program's configuration for a configuration file: the widths and
    depth of the file over the program's entry for ``arch``."""
    from repro.configs import get_arch
    base = get_arch(cfg["arch"])
    changes = {f: cfg[k] for k, f in CONFIG_FIELDS.items() if k in cfg}
    pcfg = dataclasses.replace(base, **changes)
    want = dict(family="dense", attn_kind="gqa", act="swiglu", norm="rms",
                tie_embeddings=False, moe=None, ssm=None, rglru=None,
                encoder=None)
    for k, v in want.items():
        if getattr(pcfg, k) != v:
            raise ValueError(f"{cfg['arch']}: {k}={getattr(pcfg, k)!r}, the "
                             f"{cfg['step_kind']} step needs {v!r}")
    return pcfg


def load_peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> Dict:
    with open(bench_dir / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"peaks.json; known: {sorted(table)}")
    return table[device_kind]


@dataclass
class Context:
    """What the metric readers read (``metrics/<name>.py``)."""

    cell: Cell
    seconds: float
    #: The window on the host clock.
    t0: float
    t1: float
    setup_s: float
    jobs: List[JobRecord]
    recorder: Recorder
    device_kind: str
    memory_peak_bytes: Optional[int] = None
    #: The trace reduction of a traced run (``trace.Reduction``), else None.
    trace: object = None
    _peaks: Optional[Dict] = field(default=None, repr=False)

    @property
    def batch(self) -> int:
        return int(self.cell.traffic["batch"])

    @property
    def prompt_len(self) -> int:
        return int(self.cell.traffic["prompt_len"])

    def peaks(self) -> Dict:
        if self._peaks is None:
            self._peaks = load_peaks(self.device_kind, self.cell.bench_dir)
        return self._peaks

    def in_window(self, t: float) -> bool:
        return self.t0 <= t <= self.t1

    def roofline_s(self, flops: float, nbytes: float) -> float:
        """The least time the chip could take: the larger of the bounds."""
        p = self.peaks()
        return max(flops / p["bf16_flops_per_s"],
                   nbytes / p["hbm_bytes_per_s"])

    def service_s(self, job: int) -> float:
        """A job's own service time: the sum of its block spans."""
        return sum(b.end - b.start for b in self.recorder.blocks
                   if b.job == job)


# ----------------------------------------------------------------- window
def _submit(service, rec: JobRecord, job):
    """Send ``job``; None where the service refuses it (its driver ended),
    and the job then counts as failed. The record keeps the job's token
    list and not the job, whose closure holds its KV cache."""
    rec.submitted = time.perf_counter()
    rec.generated = job.generated
    try:
        return service.submit(job)
    except RuntimeError as exc:
        rec.failed, rec.gave_up = True, time.perf_counter()
        log(f"job {rec.index} refused: {exc!r}")
        return None


async def _await_result(rec: JobRecord, handle, deadline: float,
                        abandoned: List) -> None:
    """Wait for a job's result until ``deadline``. A job given up on keeps
    its task in ``abandoned``: cancelling it would cancel the service's
    future under its driver thread."""
    if handle is None:
        return
    task = asyncio.ensure_future(handle.result())
    done, _ = await asyncio.wait(
        {task}, timeout=max(0.0, deadline - time.perf_counter()))
    if task in done and task.exception() is None:
        rec.finished = time.perf_counter()
        return
    rec.failed = True
    rec.gave_up = time.perf_counter()
    if task in done:
        log(f"job {rec.index} failed: {task.exception()!r}")
    else:
        abandoned.append(task)
        log(f"job {rec.index} did not end within {DRAIN_S}s of the close")


async def _sleep_until(t: float) -> None:
    delay = t - time.perf_counter()
    if delay > 0:
        await asyncio.sleep(delay)


async def _drive(service, cell: Cell, seed: int, build, recorder: Recorder,
                 t0: float, t1: float) -> List[JobRecord]:
    """Send the cell's jobs from ``t0`` to ``t1``, then wait for those still
    running. A traced run's trace stops at ``t1``."""
    arrivals = cell.traffic["arrivals"]
    deadline = t1 + DRAIN_S
    records: List[JobRecord] = []
    abandoned: List = []

    async def close_marker():
        await _sleep_until(t1)
        recorder.marker(WINDOW_CLOSE)
        if recorder.trace:
            await asyncio.to_thread(jax.profiler.stop_trace)

    marker = asyncio.ensure_future(close_marker())
    if arrivals["kind"] == "closed":
        sequence = tr.closed_sequence(cell.traffic, seed)

        async def client():
            while time.perf_counter() < t1:
                draw = next(sequence)
                rec = JobRecord(draw.index, draw.blocks, draw.seed,
                                time.perf_counter())
                records.append(rec)
                handle = _submit(service, rec, build(draw))
                await _await_result(rec, handle, deadline, abandoned)
                if rec.failed:
                    return

        waits = [client() for _ in range(int(arrivals["clients"]))]
    else:
        async def open_loop():
            pending = []
            for draw in tr.open_schedule(cell.traffic, seed, t1 - t0):
                job = build(draw)
                rec = JobRecord(draw.index, draw.blocks, draw.seed,
                                t0 + draw.due)
                await _sleep_until(rec.due)
                records.append(rec)
                handle = _submit(service, rec, job)
                pending.append(asyncio.ensure_future(_await_result(
                    rec, handle, deadline, abandoned)))
            await asyncio.gather(*pending)

        waits = [open_loop()]
    await asyncio.gather(*waits)
    await marker
    await service.aclose(cancel_pending=True)
    await asyncio.gather(*abandoned, return_exceptions=True)
    return sorted(records, key=lambda r: r.index)


# ------------------------------------------------------------------ check
def _sample(records: List[JobRecord], seed: int, n: int) -> List[JobRecord]:
    """The longest finished job, and ``n - 1`` more, drawn from the seed."""
    done = [r for r in records if r.finished is not None and not r.failed]
    if not done:
        return []
    r = tr.rng(seed, tr.SAMPLE)
    order = [done[i] for i in r.permutation(len(done))]
    longest = max(order, key=lambda rec: rec.blocks)
    rest = [rec for rec in order if rec is not longest]
    return [longest] + rest[:n - 1]


@jax.jit
def _widest_gap(f32, chosen, mask):
    """Widest gap by which the chosen tokens' logits lie below the best."""
    best = f32.max(-1)
    got = jnp.take_along_axis(f32, chosen[..., None], -1)[..., 0]
    return jnp.where(mask, best - got, -jnp.inf).max()


def logit_gaps(cell: Cell, weight_seed: int, served: List, *,
               control: bool = False, rows: Optional[int] = None
               ) -> Dict[str, float]:
    """Widest gap by which a compared token's reference logit lies below the
    reference's best, over every position of ``served`` (a list of
    ``reference.Sequences``). The tokens compared are the served ones; with
    ``control``, those that the control, the reference in a lower precision
    put in the program's place, puts first at the same positions, and the
    served tokens' gap is kept beside it as ``program_widest_logit_gap``.

    The reference runs at one shape per cell: sequences as long as the
    longest class's, ``rows`` rows (the sample's, by default)."""
    ref = cell.reference()
    traffic = cell.traffic
    tpb = int(cell.config["serving"]["tokens_per_block"])
    n = 1 + max(int(c["blocks"]) for c in traffic["classes"]) * tpb
    seq_len = int(traffic["prompt_len"]) + n - 1
    rows = rows or sum(s.served.shape[0] for s in served)
    chosen = np.zeros((rows, n), np.int32)
    mask = np.zeros((rows, n), bool)
    r = 0
    for s in served:
        b, k = s.served.shape
        chosen[r:r + b, :k] = s.served
        mask[r:r + b, :k] = True
        r += b
    precisions = ("float32", "float8") if control else ("float32",)
    logits = ref.served_logits(cell.config, weight_seed, served,
                               seq_len=seq_len, rows=rows,
                               precisions=precisions)
    gap = float(_widest_gap(logits["float32"], chosen, mask))
    out = {"widest_logit_gap": gap, "tokens_compared": int(mask.sum())}
    if control:
        pick = jnp.argmax(logits["float8"], -1).astype(jnp.int32)
        out["widest_logit_gap"] = float(
            _widest_gap(logits["float32"], pick, mask))
        out["program_widest_logit_gap"] = gap
    return out


# -------------------------------------------------------------------- run
@dataclass
class Outcome:
    """A finished run: the result line and the numbers behind it."""

    line: Dict
    context: Context
    check: Dict


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             device, t_process: float, control: bool = False,
             compile_cache: bool = True,
             keep_trace: Optional[str] = None) -> Outcome:
    """One run of ``cell`` on ``device``; ``t_process`` is the host clock
    when the process started (set-up is timed from it).

    ``control`` checks the control's tokens in place of the program's
    (``logit_gaps``), so that the run reads not correct;
    ``keep_trace`` copies the traced run's ``.xplane.pb`` there;
    ``compile_cache=False`` leaves JAX's persistent cache as it is."""
    from repro.core import jobs as program_jobs
    from repro.core.scheduler_service import SchedulerService
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = None
    if compile_cache:
        cache_dir = use_compile_cache()
        # Cache every program, also those that compile in under a second,
        # so that a second run of a cell finds all of its programs there.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    serving = cell.config["serving"]
    traffic = cell.traffic
    pcfg = program_config(cell.config)
    batch, prompt_len = int(traffic["batch"]), int(traffic["prompt_len"])
    tpb = int(serving["tokens_per_block"])
    weight_seed = tr.derived_seed(seed, tr.WEIGHTS)
    log(f"cell {cell.name}: {cell.config['name']} ({pcfg.n_layers} layers, "
        f"d_model {pcfg.d_model}), batch {batch}, prompt {prompt_len}, "
        f"classes {tr.cycle_classes(traffic)}, arrivals {traffic['arrivals']}"
        f"; seed {seed}; compile cache {cache_dir}")

    recorder = Recorder(trace=trace)

    def build(draw: tr.JobDraw, record: bool = True):
        job = program_jobs.make_serve_job(
            pcfg, f"c{draw.blocks}", blocks=draw.blocks,
            tokens_per_block=tpb, batch=batch, prompt_len=prompt_len,
            max_residency=1, seed=draw.seed, weight_seed=weight_seed)
        return recorder.wrap(job, draw.index, batch) if record else job

    # Set-up: the weights, then each job class once, prefill and a block.
    counter = CompileCounter()
    with counter:
        jax.block_until_ready(program_jobs.serve_params(pcfg, weight_seed))
        for k, blocks in enumerate(sorted(set(tr.cycle_classes(traffic)))):
            job = build(tr.JobDraw(-1 - k, blocks,
                                   tr.derived_seed(seed, tr.SETUP, k)),
                        record=False)
            job.warmup_fn()
            job.make_block_fn(1)()
        del job
    setup_compiles = counter.count
    service = SchedulerService(n_lanes=int(serving["lanes"]),
                               policy=serving["policy"],
                               predictor=serving["predictor"])

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(trace_dir)
    with counter:
        t0 = recorder.marker(WINDOW_OPEN)
        setup_s = t0 - t_process
        records = asyncio.run(_drive(service, cell, seed, build, recorder,
                                     t0, t0 + seconds))
    window_compiles = counter.count
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")

    # The output check, after the window: the program's state goes first.
    sample = _sample(records, seed, SAMPLE_JOBS)
    ref = cell.reference()
    served = [ref.Sequences(r.seed, prompt_len,
                            np.asarray(jnp.stack(r.generated, axis=1)))
              for r in sample]
    for r in records:
        r.generated = None
    del service, build
    program_jobs.serve_params.cache_clear()
    gc.collect()
    failed = sum(r.failed for r in records)
    t_check = time.perf_counter()
    check = {"widest_logit_gap": None, "tokens_compared": 0}
    if served:
        check = logit_gaps(cell, weight_seed, served, control=control,
                           rows=SAMPLE_JOBS * batch)
    check_s = time.perf_counter() - t_check
    limit = (cell.checks or {}).get("widest_logit_gap")
    correct = bool(served) and failed == 0 and limit is not None \
        and check["widest_logit_gap"] <= limit

    ctx = Context(cell=cell, seconds=seconds, t0=t0, t1=t0 + seconds,
                  setup_s=setup_s, jobs=records, recorder=recorder,
                  device_kind=device.device_kind, memory_peak_bytes=peak)
    breakdown = None
    if trace:
        from chipbench import trace as trace_mod
        if keep_trace:
            _keep(trace_mod.xplane_files(trace_dir), keep_trace, ctx)
        in_system = [(r.submitted - t0, r.ended - t0) for r in records
                     if r.submitted is not None and r.ended is not None]
        ctx.trace = trace_mod.reduce_dir(trace_dir, MODULES, in_system)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace: window {ctx.trace.window_s!r}s, busy "
            f"{ctx.trace.busy_s!r}s, modules {ctx.trace.module_n} "
            f"{ctx.trace.module_s}, idle by host span "
            f"{ctx.trace.idle_by_label}")
        breakdown = ctx.trace.breakdown()

    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = cell.reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    _log_summary(ctx, setup_compiles, window_compiles, check_s, check)
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": peak}
    if trace:
        dev["busy_s"] = ctx.trace.busy_s
        dev["window_s"] = ctx.trace.window_s
    checks = {
        "widest_logit_gap": {"value": check["widest_logit_gap"],
                             "limit": limit},
        "jobs_failed": {"value": failed, "limit": 0},
    }
    log(f"check widest_logit_gap {check['widest_logit_gap']!r} limit "
        f"{limit!r} over {check['tokens_compared']} served tokens of "
        f"{len(served)} jobs")
    log(f"check jobs_failed {failed} limit 0")
    line = {"correct": correct, "attempted": len(records), "failed": failed,
            "metrics": metrics, "device": dev}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return Outcome(line, ctx, check)


def _keep(files: List[str], directory: str, ctx: Context) -> None:
    """Copy a traced run's trace, with what the benchmark's own records
    say ran in its window, for a test of the trace reduction."""
    out = Path(directory)
    out.mkdir(parents=True, exist_ok=True)
    for f in files:
        shutil.copy(f, out)
    rec = ctx.recorder
    counts = {
        "window_s": ctx.seconds,
        "decode_steps": sum(b.decode_steps for b in rec.blocks
                            if ctx.in_window(b.start))
        + sum(1 for w in rec.warmups if ctx.in_window(w.start)),
        "prefills": sum(1 for b in rec.blocks
                        if b.prefill and ctx.in_window(b.start))
        + sum(1 for w in rec.warmups if ctx.in_window(w.start)),
        "blocks": sum(1 for b in rec.blocks if ctx.in_window(b.start)),
        "warmups": sum(1 for w in rec.warmups if ctx.in_window(w.start)),
    }
    (out / "records.json").write_text(json.dumps(counts, indent=1))


def _log_summary(ctx: Context, setup_compiles: int, window_compiles: int,
                 check_s: float, check: Dict) -> None:
    log(f"set-up {ctx.setup_s!r}s with {setup_compiles} programs readied; "
        f"{window_compiles} programs readied in the window and drain")
    by_class: Dict[int, List[float]] = {}
    for r in ctx.jobs:
        if r.finished is not None:
            by_class.setdefault(r.blocks, []).append(r.turnaround)
    counts = {b: sum(1 for r in ctx.jobs if r.blocks == b)
              for b in sorted({r.blocks for r in ctx.jobs})}
    log(f"jobs due in the window by class (blocks: jobs) {counts}")
    for b, ts in sorted(by_class.items()):
        log(f"  class {b} blocks: median turnaround {float(np.median(ts))!r}s"
            f" over {len(ts)} jobs")
    late = [r.submitted - r.due for r in ctx.jobs if r.submitted is not None]
    if late:
        log(f"generator lateness: max {float(max(late))!r}s mean "
            f"{float(np.mean(late))!r}s")
    log(f"output check took {check_s!r}s; {check}")
