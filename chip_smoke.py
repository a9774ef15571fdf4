"""Chip smoke test: the served scheduling path, end to end, on a TPU.

One chip (the default)::

    python chip_smoke.py

serves yi-6b at every published width with the depth cut from 32 to 8
layers: the deployment is four pipeline stages of 8 layers, and this chip
holds one stage plus the embedding and the head.  It goes through the code
that ``python -m repro.launch.serve`` runs (``build_job``, ``measure_solo``,
``run_service``): ``SchedulerService`` under SRTF with the default
structural predictor drives three serve jobs (batch 8, prompt 512, 8 tokens
per block) on one lane, because one chip is one lane.  A long job of 32
blocks arrives first; two short jobs of 4 blocks arrive while it runs.  The
check: every job's tokens under SRTF, with preemption, equal the tokens of
the same job run alone on the same weights.

Four chips (one host)::

    python chip_smoke.py --chips 4

runs only the sharded decode of the same 8-layer cut on a
``("data", "model") = (1, 4)`` mesh, with parameters created sharded, and
compares its logits over one prefill and 8 decode steps with the unsharded
program on one chip of the host, on the same weights.

Either way the script fails, and prints no result, where JAX finds no TPU;
it never falls back to the CPU.  The last line of a passing run is one JSON
object naming the device, as JAX reports it.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.configs.shapes import InputShape  # noqa: E402
from repro.core.executor import LaneExecutor  # noqa: E402
from repro.core.policies import make_policy  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_test_mesh  # noqa: E402
from repro.launch.steps import (  # noqa: E402
    build_decode_step,
    build_prefill_step,
    param_struct,
)
from repro.models import lm  # noqa: E402
from repro.sharding.specs import param_shardings  # noqa: E402

ARCH = "yi-6b"
#: yi-6b has 32 layers; one chip holds one of four 8-layer pipeline stages.
LAYERS = 8
#: The sharded logits agree with the one-chip program within this relative
#: L2 error.  Both compute in bf16 (unit roundoff 2**-9, about 2e-3), and
#: the sharded program rounds its per-device partial sums to bf16 before
#: adding them, in another order: that left about 1e-2 at the reduced
#: widths on four CPU devices, and 1.1e-2 to 1.4e-2 at 8 layers of yi-6b on
#: four v5e chips.  A lost or doubled shard gives an error of order 1, far
#: above this bound.
LOGIT_REL_TOL = 5e-2


def require_tpu(chips: int) -> jax.Device:
    """The first TPU device; exits non-zero on any other platform."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform "
            f"{devices[0].platform!r}); refusing to run on the CPU")
    if len(devices) < chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} needs {chips} TPU devices, JAX "
            f"found {len(devices)}")
    return devices[0]


class CompileCounter:
    """Counts backend compiles inside a ``with`` block (``jax.monitoring``)."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event == self._EVENT:
            self.count += 1

    def __enter__(self) -> "CompileCounter":
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def serve_args(*, layers: int, long_blocks: int, short_blocks: int,
               batch: int, prompt_len: int, tokens_per_block: int,
               seed: int = 0) -> argparse.Namespace:
    """``launch.serve`` arguments for one long job and two short ones, on
    one lane under SRTF with the default predictor."""
    jobs = f"{ARCH}:{long_blocks},{ARCH}:{short_blocks},{ARCH}:{short_blocks}"
    return serve.build_parser().parse_args([
        "--jobs", jobs, "--policy", "srtf", "--lanes", "1",
        "--batch", str(batch), "--prompt-len", str(prompt_len),
        "--tokens-per-block", str(tokens_per_block),
        "--layers", str(layers), "--seed", str(seed)])


def run_served(args: argparse.Namespace) -> dict:
    """Solo baselines, then the scheduled window, then the token check.

    Returns the numbers it printed; raises ``RuntimeError`` when the long
    job was not preempted or a job's tokens differ from its solo run.
    """
    cfg = serve.serve_config(args, ARCH)
    print(f"[smoke] config {ARCH}: d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} layers={cfg.n_layers} of "
          f"{get_arch(ARCH).n_layers}", flush=True)
    print(f"[smoke] jobs {args.jobs}: batch={args.batch} "
          f"prompt={args.prompt_len} tokens_per_block="
          f"{args.tokens_per_block} lanes={args.lanes} policy={args.policy} "
          f"predictor={args.predictor}", flush=True)

    counter = CompileCounter()
    t0 = time.perf_counter()
    with counter:
        solo = serve.measure_solo(args)
    setup_s = time.perf_counter() - t0
    print(f"[smoke] set-up {setup_s:.3f}s (weights, compiles, solo "
          f"baselines): {counter.count} backend compiles", flush=True)
    for (arch, blocks), rt in sorted(solo.items()):
        print(f"    solo {arch}:{blocks} {rt:.6f}s", flush=True)

    # The short jobs arrive an eighth and a quarter of the way into the
    # long job's solo runtime, so they find it running.
    long_blocks = serve.parse_jobs(args)[0][1]
    args.stagger = solo[(ARCH, long_blocks)] / 8
    with counter:
        run = asyncio.run(serve.run_service(args, args.policy, solo))
    window_compiles = counter.count
    order = [r.key for r in sorted(run.results, key=lambda r: r.finish)]
    long_key = f"{ARCH}#0"
    print(f"[smoke] window: {window_compiles} backend compiles; completion "
          f"order {order}", flush=True)
    for r in sorted(run.results, key=lambda r: r.key):
        print(f"    {r.key}: arrival={r.arrival:.6f}s "
              f"finish={r.finish:.6f}s turnaround={r.turnaround:.6f}s "
              f"blocks={r.blocks}", flush=True)
    m = run.metrics
    print(f"[smoke] STP={m.stp:.6f} ANTT={m.antt:.6f} "
          f"fairness={m.fairness:.6f}", flush=True)
    if order[-1] != long_key:
        raise RuntimeError(f"the long job was not preempted: order {order}")

    # Each job again, alone, on the same weights and prompt.
    mismatched = []
    for i, (arch, blocks) in enumerate(serve.parse_jobs(args)):
        key = f"{arch}#{i}"
        alone = serve.build_job(args, arch, blocks, args.seed + i)
        LaneExecutor([alone], make_policy("fifo"), n_lanes=args.lanes).run()
        got, want = run.jobs[key].tokens(), alone.tokens()
        same = got.shape == want.shape and np.array_equal(got, want)
        print(f"    {key}: tokens {got.shape} equal to solo run: {same}",
              flush=True)
        if not same:
            mismatched.append(key)
    if mismatched:
        raise RuntimeError(f"tokens under {args.policy} differ from the "
                           f"solo runs for {mismatched}")
    return {"setup_s": setup_s, "window_compiles": window_compiles,
            "order": order, "metrics": m}


def run_sharded(devices, cfg, *, batch: int, prompt_len: int,
                steps: int) -> float:
    """Sharded prefill + ``steps`` decode steps on a (1, 4) mesh against the
    unsharded program on ``devices[0]``; returns the worst relative error.

    Both runs are fed the same tokens (the sharded run's argmax), so a
    near-tie cannot send them down different sequences.
    """
    max_seq = prompt_len + steps + 8
    print(f"[smoke4] config {ARCH}: d_model={cfg.d_model} layers="
          f"{cfg.n_layers} of {get_arch(ARCH).n_layers}; mesh (data, model)"
          f"=(1, 4); batch={batch} prompt={prompt_len} decode steps={steps}",
          flush=True)
    mesh = make_test_mesh((1, 4), ("data", "model"))
    p_sh = param_shardings(param_struct(cfg), mesh)
    params = jax.jit(lambda k: lm.init(cfg, k), out_shardings=p_sh)(
        jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (batch, prompt_len),
                                0, cfg.vocab_size)
    prefill = build_prefill_step(
        cfg, InputShape("smoke", prompt_len, batch, "prefill"), mesh=mesh,
        max_seq=max_seq)
    decode = build_decode_step(
        cfg, InputShape("smoke", max_seq, batch, "decode"), mesh=mesh)

    with mesh:
        logits, caches = prefill.fn(params, {"tokens": prompt})
        sharded = [logits]
        tokens = [jnp.argmax(logits, -1).astype(jnp.int32)]
        lengths = jnp.full((batch,), prompt_len, jnp.int32)
        for _ in range(steps):
            logits, caches = decode.fn(params, tokens[-1], caches, lengths)
            lengths = lengths + 1
            sharded.append(logits)
            tokens.append(jnp.argmax(logits, -1).astype(jnp.int32))
        sharded = [np.asarray(x, np.float32) for x in sharded]
        tokens = [np.asarray(t) for t in tokens]
    del caches
    for d in devices[:4]:
        stats = d.memory_stats() or {}
        print(f"    device {d.id}: peak_bytes_in_use="
              f"{stats.get('peak_bytes_in_use')}", flush=True)

    one = jax.device_put(params, SingleDeviceSharding(devices[0]))
    del params
    one_prefill = build_prefill_step(
        cfg, InputShape("smoke", prompt_len, batch, "prefill"),
        max_seq=max_seq)
    one_decode = build_decode_step(
        cfg, InputShape("smoke", max_seq, batch, "decode"))
    logits, caches = one_prefill.fn(one, {"tokens": prompt})
    reference = [logits]
    lengths = jnp.full((batch,), prompt_len, jnp.int32)
    for tok in tokens[:steps]:
        logits, caches = one_decode.fn(one, tok, caches, lengths)
        lengths = lengths + 1
        reference.append(logits)
    reference = [np.asarray(x, np.float32) for x in reference]

    worst = 0.0
    for i, (got, want) in enumerate(zip(sharded, reference)):
        rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
        name = "prefill" if i == 0 else f"decode {i}"
        print(f"    {name}: relative L2 error {rel:.3e}, argmax agreement "
              f"{agree:.3f}, finite {bool(np.isfinite(got).all())}",
              flush=True)
        if not np.isfinite(got).all():
            raise RuntimeError(f"non-finite sharded logits at {name}")
        worst = max(worst, rel)
    print(f"[smoke4] worst relative error {worst:.3e} (tolerance "
          f"{LOGIT_REL_TOL})", flush=True)
    if worst > LOGIT_REL_TOL:
        raise RuntimeError(f"sharded logits differ from the one-chip "
                           f"program: {worst:.3e} > {LOGIT_REL_TOL}")
    return worst


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded decode on four chips and "
                         "its one-chip comparison")
    args = ap.parse_args(argv)
    device = require_tpu(args.chips)
    print(f"[smoke] platform={device.platform} device_kind="
          f"{device.device_kind} device_count={len(jax.devices())} "
          f"compile_cache={use_compile_cache()}", flush=True)

    if args.chips == 4:
        run_sharded(jax.devices(),
                    dataclasses.replace(get_arch(ARCH), n_layers=LAYERS),
                    batch=8, prompt_len=512, steps=8)
    else:
        run_served(serve_args(layers=LAYERS, long_blocks=32, short_blocks=4,
                              batch=8, prompt_len=512, tokens_per_block=8))
        stats = device.memory_stats() or {}
        print(f"[smoke] peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
              f"bytes_limit={stats.get('bytes_limit')}", flush=True)

    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
