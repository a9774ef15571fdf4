"""Chip benchmark of the served scheduling path.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

runs one cell of ``BENCHMARK.json`` on the chip it is started on: set-up
(weights from the seed, each job class warmed once), then ``--seconds`` of
jobs sent to ``SchedulerService`` as the cell's traffic mix says, then the
check of the window's tokens against the plain reference. The last line of
standard output is one JSON object: with ``--trace 0`` the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window. Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.

``control.py`` is the tool that sets a cell's limits and finds its load.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from chipbench import catalog  # noqa: E402


def require_chip(chips: int):
    """The first TPU device; exits non-zero on any other platform."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: JAX found no TPU (platform "
                         f"{devices[0].platform!r}); no result on the CPU")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"found {len(devices)}")
    return devices[0]


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True,
                    help="a cell of BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> None:
    args = parse(argv)
    import repro  # noqa: F401  (the system under test must be there)

    cell = catalog.find_cell(args.workload)
    device = require_chip(cell.chips)
    from chipbench.harness import run_cell
    outcome = run_cell(cell, seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace), device=device,
                       t_process=T_PROCESS)
    print(json.dumps(outcome.line), flush=True)


if __name__ == "__main__":
    main()
