"""End-to-end training driver.

Single-job mode (default): train one architecture for N steps with the full
substrate stack — deterministic data pipeline, AdamW, async checkpointing,
restart (``--resume``), step-time telemetry feeding the structural
predictor's staircase estimate of job completion.

Multi-job mode (``--jobs a,b,...``): the paper's scenario — concurrent
training jobs scheduled on the lane executor under ``--policy``
(fifo|mpmax|srtf|srtf-adaptive), with preemption at step boundaries.

Reduced configs run on CPU; pass ``--full`` only on a real pod (the full
configs are exercised via launch.dryrun on this container).

Examples::

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --steps 20
    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --steps 50 \
        --checkpoint-dir /tmp/ck --checkpoint-every 10 --resume
    PYTHONPATH=src python -m repro.launch.train \
        --jobs yi-6b:30,mamba2-2.7b:12 --policy srtf
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import ARCHS, get_arch
from repro.configs.shapes import InputShape
from repro.core.executor import LaneExecutor
from repro.core.jobs import make_train_job
from repro.core.metrics import evaluate
from repro.core.policies import make_policy
from repro.core.predictor import staircase_runtime
from repro.data import pipeline as data
from repro.launch.compile_cache import use_compile_cache
from repro.launch.steps import build_train_step
from repro.models import lm
from repro.optim import adamw


def train_single(args) -> None:
    cfg = get_arch(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    shape = InputShape("train_cli", args.seq, args.batch, "train")
    opt_cfg = adamw.OptConfig(lr=args.lr, warmup_steps=max(2, args.steps // 10),
                              total_steps=max(args.steps, 2))
    bundle = build_train_step(cfg, shape, mesh=None, opt_cfg=opt_cfg,
                              remat=False)

    ck = None
    start_step = 0
    key = jax.random.PRNGKey(args.seed)
    params = lm.init(cfg, key)
    opt_state = adamw.init(params)
    if args.checkpoint_dir:
        ck = Checkpointer(args.checkpoint_dir)
        if args.resume and ck.latest_step() is not None:
            start_step, state, _ = ck.restore(
                {"params": params, "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            print(f"[train] resumed from step {start_step}")

    t_first = None
    t_accum = 0.0
    for step in range(start_step, args.steps):
        batch = data.batch_for_step(cfg, shape, step,
                                    data.DataConfig(seed=args.seed))
        t0 = time.perf_counter()
        params, opt_state, metrics = bundle.fn(params, opt_state, batch)
        jax.block_until_ready(metrics["nll"])
        dt = time.perf_counter() - t0
        t_accum += dt
        if t_first is None and step == start_step + 1:
            # structural runtime prediction for the whole job (Eq. 1 with
            # R=1 lane): profile one steady-state step, extrapolate.
            t_first = dt
            pred = staircase_runtime(args.steps - step, 1, dt)
            print(f"[predictor] t={dt:.3f}s/step -> predicted remaining "
                  f"{pred:.1f}s for {args.steps - step} steps")
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"[train] step={step} nll={float(metrics['nll']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"lr={float(metrics['lr']):.2e} {dt:.3f}s")
        if ck is not None and args.checkpoint_every and \
                (step + 1) % args.checkpoint_every == 0:
            ck.save(step + 1, {"params": params, "opt": opt_state},
                    {"arch": args.arch})
    if ck is not None:
        ck.save(args.steps, {"params": params, "opt": opt_state},
                {"arch": args.arch})
        ck.wait()
    print(f"[train] done: {args.steps - start_step} steps, "
          f"{t_accum:.1f}s compute")


def train_multi(args) -> None:
    specs = []
    for i, item in enumerate(args.jobs.split(",")):
        arch_id, _, blocks = item.partition(":")
        cfg = get_arch(arch_id).reduced()
        specs.append(make_train_job(
            cfg, arch_id, blocks=int(blocks or 20), batch=args.batch,
            seq=args.seq, max_residency=args.lanes, seed=args.seed + i,
            arrival=0.05 * i, tenant=arch_id))
    # Solo baselines: one warmed job per distinct (arch, blocks) item,
    # measured once.  Job keys are "{arch}#{order}"; split on the last '#'
    # to recover the arch.
    solo = {}
    blocks_of = {}
    for order, js in enumerate(specs):
        blocks_of[f"{js.name}#{order}"] = js.num_blocks
        if (js.name, js.num_blocks) in solo:
            continue
        fresh = make_train_job(
            ARCHS[js.name].reduced(), js.name, blocks=js.num_blocks,
            batch=args.batch, seq=args.seq, max_residency=args.lanes,
            seed=args.seed)
        res = LaneExecutor(
            [fresh], make_policy("fifo"), n_lanes=args.lanes).run()
        solo[(js.name, js.num_blocks)] = next(iter(res.values())).turnaround
    ex = LaneExecutor(specs, make_policy(args.policy), n_lanes=args.lanes,
                      predictor=args.predictor)
    # SJF-style oracles are per kernel name; use the first item's baseline.
    for (name, _), rt in solo.items():
        ex.oracle_runtimes.setdefault(name, rt)
    results = ex.run()
    turnaround = {k: r.turnaround for k, r in results.items()}
    solo_map = {k: solo[(k.rsplit("#", 1)[0], blocks_of[k])]
                for k in turnaround}
    m = evaluate(turnaround, solo_map)
    print(f"[multi] policy={args.policy} STP={m.stp:.3f} ANTT={m.antt:.3f} "
          f"fairness={m.fairness:.3f}")
    for k, r in results.items():
        print(f"  {k}: turnaround={r.turnaround:.2f}s blocks={r.blocks}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="yi-6b", choices=sorted(ARCHS))
    ap.add_argument("--jobs", default=None,
                    help="multi-job mode: arch:blocks,arch:blocks,...")
    ap.add_argument("--policy", default="srtf")
    ap.add_argument("--predictor", default="simple-slicing",
                    help="registered predictor name (simple-slicing, ewma)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--full", action="store_true",
                    help="use the full (pod-scale) config — real pods only")
    args = ap.parse_args()
    use_compile_cache()
    if args.jobs:
        train_multi(args)
    else:
        train_single(args)


if __name__ == "__main__":
    main()
