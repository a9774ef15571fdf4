"""Times the routed SwiGLU of DeepSeek-V2-Lite's expert layer as grouped
products on a TPU: ``jax.lax.ragged_dot`` against megablox ``gmm`` at a few
tilings, at the rows of a decode step (batch 8, top-6 of 64 experts: 48
rows) and of a prefill (8 prompts of 512 tokens: 24576 rows).  Each call
reads one layer's 64 experts of 2048 x 1408 in place from a stack of four
layers, as the served expert layer does (``ops.moe_apply`` with ``layer``).
A tool beside the benchmark, as ``control.py`` is: no cell runs it.

    python3 chipbench/grouped_products.py [--reps 30]

Prints one JSON line per (shape, variant): the median milliseconds of a
call (three grouped products and the SiLU gate), and its roofline time at
the v5e peaks, counting the experts the rows touch.  Needs a TPU; exits
non-zero elsewhere.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import ops  # noqa: E402

D, F, E, K, LAYERS = 2048, 1408, 64, 6, 4
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9
#: (tm, tk, tn) tilings of gmm tried at each shape; tm None is the rows of
#: the call rounded up to 16.
TILINGS = {
    "decode": [(None, 512, 1408), (None, 1024, 1408), (None, 2048, 512),
               (None, 256, 1408)],
    "prefill": [(512, 1024, 1408), (256, 1024, 1408), (512, 512, 1408),
                (128, 1024, 1408), (256, 2048, 512)],
}


def _inputs(tokens: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    experts = np.argsort(rng.random((tokens, E)), axis=1)[:, :K]
    flat = np.sort(experts.reshape(-1)) + E        # layer 1 of the stack
    sizes = np.bincount(flat, minlength=LAYERS * E).astype(np.int32)
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    rows = jax.random.normal(ks[0], (flat.size, D), jnp.bfloat16)
    w1 = jax.random.normal(ks[1], (LAYERS * E, D, F), jnp.bfloat16) * 0.02
    w2 = jax.random.normal(ks[2], (LAYERS * E, D, F), jnp.bfloat16) * 0.02
    w3 = jax.random.normal(ks[3], (LAYERS * E, F, D), jnp.bfloat16) * 0.02
    touched = int((sizes > 0).sum())
    return rows, w1, w2, w3, jnp.asarray(sizes), touched


def _swiglu(product):
    def f(rows, w1, w2, w3, sizes):
        h = product(rows, w1, sizes)
        u = product(rows, w2, sizes)
        a = jax.nn.silu(h.astype(jnp.float32)).astype(rows.dtype) * u
        return product(a, w3, sizes)
    return jax.jit(f)


def _time(fn, args, reps: int) -> float:
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t)
    return statistics.median(times)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=30)
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        raise SystemExit("grouped_products: needs a TPU")
    for shape, tokens in (("decode", 8), ("prefill", 4096)):
        rows, w1, w2, w3, sizes, touched = _inputs(tokens)
        m = rows.shape[0]
        roof = max(m * 6 * D * F / PEAK_FLOPS,
                   touched * 3 * D * F * 2 / PEAK_BYTES)
        variants = {"ragged_dot": jax.lax.ragged_dot}
        for tiling in TILINGS[shape]:
            def product(lhs, rhs, s, tiling=tiling):
                return ops.grouped_product(lhs, rhs, s, tiling=tiling)
            variants[f"gmm{tiling}"] = product
        ref = None
        for name, product in variants.items():
            fn = _swiglu(product)
            out = np.asarray(fn(rows, w1, w2, w3, sizes), np.float32)
            ref = out if ref is None else ref
            err = float(np.abs(out - ref).max() / np.abs(ref).max())
            s = _time(fn, (rows, w1, w2, w3, sizes), args.reps)
            print(json.dumps({"shape": shape, "rows": m, "touched": touched,
                              "variant": name, "ms": s * 1e3,
                              "roofline_ms": roof * 1e3,
                              "roofline_share": roof / s,
                              "max_rel_diff_vs_ragged_dot": err}),
                  flush=True)


if __name__ == "__main__":
    main()
