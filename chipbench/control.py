"""The tool that sets a cell's limits: readings of its output check, and
runs of pairs that no cell names yet, seed by seed, in one process.

    python3 chipbench/control.py --workload <cell> --seconds <s> \\
        --seeds <n> [<n> ...] [--trace 1] [--keep-trace DIR]
    python3 chipbench/control.py --config <name> --traffic <name> ...

Each seed is a whole run of the cell at its own load (``--seconds`` of
window, long enough to finish the mix's longest jobs) with the control in
the program's place: the output check compares the tokens that the
reference in float8 puts first at the served positions, and the run reads
``correct`` false where the check separates the two. The served tokens'
own gap is read in the same pass. One JSON line per seed, with the run's
metrics, then a summary line with the largest program reading and the
smallest control reading. The benchmark's own runs never run the control.

``--config --traffic`` runs a configuration under a mix that no cell names
(the closed-loop runs that find a knee), with every metric and no limit;
``--keep-trace DIR`` keeps a traced run's trace and the window's counts
(how ``data/trace-yi6b-closed4.*`` was recorded). It needs the chip, as
``run.py`` does.
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

from chipbench import catalog  # noqa: E402
from chipbench.run import require_chip  # noqa: E402


def adhoc_cell(config: str, traffic: str) -> catalog.Cell:
    """A configuration under a traffic mix that no cell names yet: every
    metric of ``BENCHMARK.json``, no limits."""
    bench = catalog.load_benchmark()
    return catalog.Cell(
        name=f"{config}.{traffic}", chips=1,
        config=catalog.load_json("configs", config),
        traffic=catalog.load_json("traffic", traffic), checks=None,
        end_to_end=list(bench["end_to_end"]),
        per_layer=list(bench["per_layer"]))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", help="a cell of BENCHMARK.json")
    ap.add_argument("--config", help="with --traffic: a pair no cell names")
    ap.add_argument("--traffic")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="copy the traced run's .xplane.pb into DIR")
    args = ap.parse_args(argv)
    if (args.workload is None) == (args.config is None) \
            or (args.config is None) != (args.traffic is None):
        ap.error("give --workload, or --config with --traffic")
    import repro  # noqa: F401

    cell = (catalog.find_cell(args.workload) if args.workload
            else adhoc_cell(args.config, args.traffic))
    device = require_chip(cell.chips)
    from chipbench.harness import run_cell
    program, control = [], []
    t_process = T_PROCESS
    for seed in args.seeds:
        out = run_cell(cell, seed=seed, seconds=args.seconds,
                       trace=bool(args.trace), device=device,
                       t_process=t_process, control=True,
                       keep_trace=args.keep_trace)
        t_process = time.perf_counter()
        program.append(out.check.get("program_widest_logit_gap"))
        control.append(out.check["widest_logit_gap"])
        print(json.dumps({"seed": seed, "program": program[-1],
                          "control": control[-1],
                          "correct": out.line["correct"],
                          "tokens": out.check["tokens_compared"],
                          "failed": out.line["failed"],
                          "metrics": out.line["metrics"],
                          "peak": out.line["device"]["memory_peak_bytes"]}),
              flush=True)
    print(json.dumps({"workload": cell.name, "seeds": len(args.seeds),
                      "program_max": max(filter(None, program),
                                         default=None),
                      "control_min": min(filter(None, control),
                                         default=None),
                      "device": device.device_kind}), flush=True)


if __name__ == "__main__":
    main()
