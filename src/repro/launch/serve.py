"""Concurrent serving driver — the paper's scenario on the serving side.

Multiple decode jobs (request batches with different generation lengths)
share the machine under a thread-block-style scheduling policy.  Jobs are
submitted **asynchronously** through the multi-tenant
:class:`repro.core.scheduler_service.SchedulerService`: each arrives
``--stagger`` seconds after the previous one while the machine is already
running — the dynamic-arrival path the production story needs, not a fixed
up-front job list.  The structural predictor profiles each job's first
decode chunk and SRTF runs the predicted-shortest job first, preempting at
chunk boundaries; STP/ANTT are reported per tenant (one tenant per arch).

Key convention: job keys are ``{arch}#{order}`` — the text before the last
``#`` is the arch/tenant name (recover it with ``key.rsplit("#", 1)[0]``),
the number after is the machine-wide submission order.  Solo baselines are
measured once per distinct (arch, blocks) item and mapped to job keys at
submission time.

Submission pacing comes from the scenario registry
(:mod:`repro.core.scenarios`) when ``--scenario`` is given: the named
arrival process (``poisson-open`` open-loop streams, ``bursty`` ON/OFF
traffic, ...) is sampled at ``--seed`` and its arrival times, scaled by
``--time-scale`` seconds/cycle, pace the async submissions.  Without it,
jobs arrive every ``--stagger`` seconds (the paper's staggered launches).

With ``--scenario-kernels`` the scenario supplies the *jobs* too, not just
the pacing: its first workload's arrivals are bridged to jobs of real
jitted synthetic blocks (:func:`repro.core.scenarios.executor_workload` —
the same bridge executor sweeps use), and solo baselines go through the
content-addressed sweep cache
(:func:`repro.core.sweep.solo_runtime_executor_cached`), so repeated
serving runs reuse them.  Baselines are keyed by spec content plus the
pool width they were measured under, and ``--max-blocks`` rewrites the
specs before bridging — so they are shared with executor *sweeps* only
when the grids match (e.g. ``--max-blocks 0``, or a scenario whose
declared grids are already small) AND the sweep ran serially
(``--jobs 1``): a ``--jobs > 1`` sweep caches pool-contention-measured
baselines under a different key, which this serial frontend deliberately
does not reuse.

**Closed-loop driver** (``--closed-loop N``): instead of pacing
submissions open-loop, ``N`` client coroutines each hold one job in
flight — submit, await completion, optionally think ``Exp(--think)``
seconds, resubmit — until ``--requests`` total jobs complete.  This
exercises the async service at a *target concurrency* (the serving mirror
of the ``think-time``/``mgk-closed`` sweep scenarios): offered load
tracks service capacity, which is where preemptive SRTF earns or loses
its win.  Reported metrics are the steady-state queueing view
(:func:`repro.core.metrics.evaluate_queueing` over machine-time
arrivals/finishes) plus the usual STP/ANTT.

Jobs are built from the ``reduced()`` CPU configuration unless
``--layers N`` asks for the published widths with the depth cut to ``N``
layers (the one-chip share that ``chip_smoke.py`` serves).  Jobs of one
configuration share one weight set drawn from ``--seed``; each job's prompt
has a seed of its own.

Example::

    PYTHONPATH=src python -m repro.launch.serve \
        --jobs yi-6b:24,minicpm3-4b:6 --policy srtf --compare-fifo
    PYTHONPATH=src python -m repro.launch.serve \
        --jobs yi-6b:8,minicpm3-4b:4,yi-6b:8 --scenario poisson-open \
        --time-scale 2e-7 --policy srtf
    PYTHONPATH=src python -m repro.launch.serve \
        --scenario poisson-open --scenario-kernels --policy srtf \
        --time-scale 1e-6
    PYTHONPATH=src python -m repro.launch.serve \
        --jobs yi-6b:6,minicpm3-4b:4 --closed-loop 3 --requests 12 \
        --policy srtf --compare-fifo
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import itertools
from typing import Callable, Dict, List, NamedTuple, Tuple

from repro.configs import get_arch
from repro.configs.base import ArchConfig
from repro.core.executor import ExecutorJob, JobResult, LaneExecutor
from repro.core.jobs import make_serve_job
from repro.core.metrics import WorkloadMetrics, evaluate, evaluate_queueing
from repro.core.policies import make_policy
from repro.core.scenarios import (
    executor_job,
    make_scenario,
    open_loop_names,
    submission_offsets,
)
from repro.core.scheduler_service import SchedulerService
from repro.core.sweep import solo_runtime_executor_cached
from repro.core.workload import Arrival, scaled_spec
from repro.launch.compile_cache import use_compile_cache


def parse_jobs(args) -> List[Tuple[str, int]]:
    out = []
    for item in args.jobs.split(","):
        arch_id, _, blocks = item.partition(":")
        out.append((arch_id, int(blocks or 8)))
    return out


def serve_config(args, arch_id: str) -> ArchConfig:
    """``--layers N``: published widths, ``N`` layers; else ``reduced()``."""
    cfg = get_arch(arch_id)
    if args.layers:
        return dataclasses.replace(cfg, n_layers=args.layers)
    return cfg.reduced()


def build_job(args, arch_id: str, blocks: int, seed: int):
    """One serve job; ``seed`` draws its prompt, and every job of the arch
    shares the weight set of ``--seed``."""
    return make_serve_job(
        serve_config(args, arch_id), arch_id, blocks=blocks,
        tokens_per_block=args.tokens_per_block, batch=args.batch,
        prompt_len=args.prompt_len, max_residency=args.lanes,
        seed=seed, weight_seed=args.seed, tenant=arch_id)


def scenario_arrivals(args):
    """First-workload arrivals of the ``--scenario`` arrival process.

    Grids are capped at ``--max-blocks`` before bridging: scenario specs
    declare simulator-scale grids (thousands of blocks), and every bridged
    block is a real measured execution — a serving demo wants seconds, not
    hours.  The cap rescales ``num_blocks`` only; the per-block cost and
    kernel mix stay scenario-declared.
    """
    scn = make_scenario(args.scenario, seed=args.seed)
    workloads = scn.workloads()
    if not workloads:
        raise ValueError(f"scenario {scn.name!r} produced no workloads")
    arrivals = workloads[0][1]
    cap = args.max_blocks
    if cap:
        arrivals = [
            Arrival(scaled_spec(a.spec,
                                num_blocks=min(a.spec.num_blocks, cap)),
                    a.time, uid=a.uid)
            for a in arrivals
        ]
    return arrivals


def measure_solo(args) -> Dict[object, float]:
    """Measured isolated runtime per distinct job — the STP/ANTT baseline.

    One warmed job object per distinct (arch, blocks) item, measured once
    and reused by every policy run: rebuilding a job per policy would
    re-trace and re-JIT its step functions and re-pay prefill, so the
    baseline would drift between the ``--policy`` and ``--compare-fifo``
    runs of the same invocation.  Keyed by (arch, blocks), not arch alone:
    the same arch listed with a different decode length is a different
    job and needs its own baseline.

    With ``--scenario-kernels`` the baselines are keyed by the scenario's
    kernel specs and go through the content-addressed sweep cache, shared
    with executor sweeps of the same scenario.
    """
    if args.scenario_kernels:
        return {a.spec: solo_runtime_executor_cached(
                    a.spec, n_lanes=args.lanes, cache_dir=args.cache_dir)
                for a in scenario_arrivals(args)}
    solo: Dict[object, float] = {}
    for arch_id, blocks in parse_jobs(args):
        if (arch_id, blocks) in solo:
            continue                  # one baseline per distinct item
        job = build_job(args, arch_id, blocks, args.seed)
        res = LaneExecutor([job], make_policy("fifo"),
                           n_lanes=args.lanes).run()
        solo[(arch_id, blocks)] = next(iter(res.values())).turnaround
    return solo


def submission_schedule(args) -> List[float]:
    """Per-job submission offsets (seconds since the first submission).

    Default: a fixed ``--stagger`` gap, the paper's staggered launches.
    With ``--scenario`` the offsets come from the named arrival process in
    the scenario registry (e.g. ``poisson-open`` for shared-cloud open-loop
    streams), scaled by ``--time-scale`` seconds per cycle.
    """
    n = len(parse_jobs(args))
    if not args.scenario:
        return [i * args.stagger for i in range(n)]
    return submission_offsets(args.scenario, n, time_scale=args.time_scale,
                              seed=args.seed)


def submission_plan(args, solo: Dict[object, float]
                    ) -> List[Tuple[float, Callable, str, float]]:
    """Per-submission ``(offset_s, job_factory, tenant, solo_runtime)``.

    The default path builds arch-model jobs from ``--jobs``; with
    ``--scenario-kernels`` the scenario's own arrivals are bridged to
    synthetic real-jitted jobs, keeping its kernel mix and arrival times.
    """
    if args.scenario_kernels:
        return [
            (a.time * args.time_scale,
             lambda a=a: executor_job(a, n_lanes=args.lanes,
                                      time_scale=args.time_scale),
             a.spec.name, solo[a.spec])
            for a in scenario_arrivals(args)
        ]
    offsets = submission_schedule(args)
    return [
        (offsets[i],
         lambda arch_id=arch_id, blocks=blocks, i=i: build_job(
             args, arch_id, blocks, args.seed + i),
         arch_id, solo[(arch_id, blocks)])
        for i, (arch_id, blocks) in enumerate(parse_jobs(args))
    ]


class ServiceRun(NamedTuple):
    """What one policy run produced: metrics, per-job results and the jobs
    themselves (keyed like the results)."""

    metrics: WorkloadMetrics
    results: List[JobResult]
    jobs: Dict[str, ExecutorJob]


async def run_service(args, policy: str,
                      solo: Dict[object, float]) -> ServiceRun:
    """One policy run: staggered async submissions against a live service."""
    service = SchedulerService(n_lanes=args.lanes, policy=policy,
                               predictor=args.predictor)
    plan = submission_plan(args, solo)
    try:
        handles = []
        jobs: Dict[str, ExecutorJob] = {}
        solo_by_key: Dict[str, float] = {}
        t0 = asyncio.get_event_loop().time()
        for offset, job_factory, tenant, solo_rt in plan:
            delay = t0 + offset - asyncio.get_event_loop().time()
            if delay > 0:
                await asyncio.sleep(delay)  # late arrival, busy machine
            job = job_factory()
            handle = service.submit(job, tenant=tenant,
                                    solo_runtime=solo_rt)
            jobs[handle.key] = job
            solo_by_key[handle.key] = solo_rt
            handles.append(handle)
        results = [await h.result() for h in handles]
    finally:
        service.close()

    turnaround = {r.key: r.turnaround for r in results}
    m = evaluate(turnaround, solo_by_key)
    print(f"[serve] policy={policy:14s} STP={m.stp:.3f} ANTT={m.antt:.3f} "
          f"fairness={m.fairness:.3f}")
    print_tenant_report(service)
    for r in sorted(results, key=lambda r: r.key):
        print(f"    {r.key}: turnaround={r.turnaround:.2f}s")
    return ServiceRun(m, results, jobs)


def print_tenant_report(service: SchedulerService) -> None:
    for tenant, info in sorted(service.tenant_report().items()):
        tm = info["metrics"]
        if tm is not None:
            print(f"    tenant={tenant}: jobs={info['jobs']} "
                  f"STP={tm['stp']:.3f} ANTT={tm['antt']:.3f}")


def closed_loop_items(args, solo: Dict[object, float]):
    """The job menu closed-loop clients cycle through: per-item
    ``(make(i) -> job, tenant, solo_runtime)``.

    Arrival *times* are deliberately absent — in closed-loop mode pacing
    comes from completions (and ``--think``), not from a scenario clock —
    so scenario-kernel jobs are bridged at arrival time 0 and submitted
    whenever a client's previous job finishes.
    """
    if args.scenario_kernels:
        return [
            (lambda i, a=a: executor_job(
                Arrival(a.spec, 0.0), n_lanes=args.lanes,
                time_scale=args.time_scale),
             a.spec.name, solo[a.spec])
            for a in scenario_arrivals(args)
        ]
    return [
        (lambda i, arch_id=arch_id, blocks=blocks: build_job(
            args, arch_id, blocks, args.seed + i),
         arch_id, solo[(arch_id, blocks)])
        for arch_id, blocks in parse_jobs(args)
    ]


async def run_service_closed_loop(args, policy: str,
                                  solo: Dict[object, float]):
    """One closed-loop policy run: ``--closed-loop`` concurrent clients,
    each looping submit -> await -> think, against a live service."""
    import numpy as np

    service = SchedulerService(n_lanes=args.lanes, policy=policy,
                               predictor=args.predictor)
    items = closed_loop_items(args, solo)
    counter = itertools.count()
    results = []
    solo_by_key: Dict[str, float] = {}

    async def client(cid: int) -> None:
        rng = np.random.default_rng((args.seed, cid))
        while True:
            i = next(counter)
            if i >= args.requests:
                return
            if args.think > 0.0:
                await asyncio.sleep(float(rng.exponential(args.think)))
            make, tenant, solo_rt = items[i % len(items)]
            handle = service.submit(make(i), tenant=tenant,
                                    solo_runtime=solo_rt)
            solo_by_key[handle.key] = solo_rt
            results.append(await handle.result())

    try:
        await asyncio.gather(
            *(client(c) for c in range(args.closed_loop)))
    finally:
        service.close()

    # Machine-time (virtual-clock) arrivals/finishes: the queueing view is
    # of the machine under load, not of wall-clock client latency.
    q = evaluate_queueing({r.key: r.arrival for r in results},
                          {r.key: r.finish for r in results},
                          end_time=service.machine_time,
                          warmup_frac=args.warmup_frac)
    m = evaluate({r.key: r.turnaround for r in results}, solo_by_key)
    print(f"[serve] policy={policy:14s} closed-loop={args.closed_loop} "
          f"requests={q.n_completed} mean_rt={q.mean_response:.3f}s "
          f"p95_rt={q.p95_response:.3f}s in_system={q.mean_in_system:.2f} "
          f"xput={q.throughput:.2f}/s")
    print(f"    STP={m.stp:.3f} ANTT={m.antt:.3f} "
          f"fairness={m.fairness:.3f}")
    print_tenant_report(service)
    return q


def run_policy(args, policy: str, solo: Dict[Tuple[str, int], float]):
    return asyncio.run(run_service(args, policy, solo)).metrics


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--jobs", default="yi-6b:24,minicpm3-4b:6",
                    help="arch:decode_blocks,...")
    ap.add_argument("--policy", default="srtf")
    ap.add_argument("--predictor", default="simple-slicing",
                    help="registered predictor name (simple-slicing, ewma)")
    ap.add_argument("--compare-fifo", action="store_true")
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens-per-block", type=int, default=8)
    ap.add_argument("--stagger", type=float, default=0.02,
                    help="seconds between async job submissions")
    ap.add_argument("--closed-loop", type=int, default=0,
                    help="drive the service closed-loop at this target "
                         "concurrency (N clients, each resubmitting when "
                         "its job finishes; 0 = open-loop pacing)")
    ap.add_argument("--requests", type=int, default=12,
                    help="total jobs a closed-loop run completes")
    ap.add_argument("--think", type=float, default=0.0,
                    help="mean Exp think seconds between a closed-loop "
                         "client's completion and its next submission")
    ap.add_argument("--warmup-frac", type=float, default=0.0,
                    help="fraction of the closed-loop window trimmed "
                         "before computing queueing metrics")
    # trace-replay is excluded (it needs a path/trace the CLI doesn't
    # take); closed-loop scenarios are excluded because this flag paces a
    # fixed submission stream — closed-loop serving is --closed-loop.
    ap.add_argument("--scenario", default=None,
                    choices=sorted(set(open_loop_names()) - {"trace-replay"}),
                    help="draw submission offsets from this registered "
                         "arrival process instead of a fixed stagger "
                         "(e.g. poisson-open, bursty)")
    ap.add_argument("--time-scale", type=float, default=1e-6,
                    help="seconds of wall time per scenario cycle "
                         "(with --scenario)")
    ap.add_argument("--scenario-kernels", action="store_true",
                    help="with --scenario: take the jobs themselves from "
                         "the scenario via the executor bridge (synthetic "
                         "real-jitted blocks) instead of --jobs archs")
    ap.add_argument("--cache-dir", default="artifacts/sweep_cache",
                    help="sweep cache for --scenario-kernels solo "
                         "baselines (shared with jobs=1 executor sweeps)")
    ap.add_argument("--max-blocks", type=int, default=16,
                    help="cap scenario grids at this many real blocks per "
                         "job (with --scenario-kernels; 0 = uncapped)")
    ap.add_argument("--layers", type=int, default=0,
                    help="serve the published widths with the depth cut to "
                         "this many layers (0 = the reduced CPU config)")
    ap.add_argument("--seed", type=int, default=0,
                    help="weight seed; job i draws its prompt from seed+i")
    return ap


def main() -> None:
    ap = build_parser()
    args = ap.parse_args()
    if args.scenario_kernels and not args.scenario:
        ap.error("--scenario-kernels requires --scenario")
    use_compile_cache()
    solo = measure_solo(args)
    if args.closed_loop > 0:
        q = asyncio.run(run_service_closed_loop(args, args.policy, solo))
        if args.compare_fifo and args.policy != "fifo":
            qf = asyncio.run(run_service_closed_loop(args, "fifo", solo))
            print(f"[serve] {args.policy} vs fifo at concurrency "
                  f"{args.closed_loop}: mean_rt "
                  f"{qf.mean_response / q.mean_response:.2f}x, p95_rt "
                  f"{qf.p95_response / q.p95_response:.2f}x")
        return
    m = run_policy(args, args.policy, solo)
    if args.compare_fifo and args.policy != "fifo":
        mf = run_policy(args, "fifo", solo)
        print(f"[serve] {args.policy} vs fifo: STP {m.stp / mf.stp:.2f}x, "
              f"ANTT {mf.antt / m.antt:.2f}x")


if __name__ == "__main__":
    main()
