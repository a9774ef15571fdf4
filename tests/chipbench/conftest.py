"""Shared set-up of the chip benchmark's tests.

They run on the CPU. ``bench_copy`` copies the benchmark into a temporary
directory and adds a configuration at ``reduced()``-like widths, two
traffic mixes and two cells of them, with an output-check limit read at
those widths on the CPU, so that the harness body runs end to end in
seconds.
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

#: The widths of ``ArchConfig.reduced()`` for a dense GQA model, with the
#: published yi-6b keys around them.
TINY = {"name": "tiny-gqa", "hidden_size": 128, "intermediate_size": 256,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "vocab_size": 512}
#: Widest logit gap allowed at the tiny widths. On the CPU, over seven
#: seeds, the program's bfloat16 tokens (at its RMS norm epsilon of 1e-6)
#: read 7.0e-3 to 2.1e-2 against the float32 reference (at the published
#: 1e-5), the float8 control 0.080 to 0.36.
TINY_LIMIT = 0.045


def _tiny_traffic(arrivals):
    return {"classes": [{"blocks": 1, "per_cycle": 2},
                        {"blocks": 3, "per_cycle": 1}],
            "batch": 2, "prompt_len": 16, "arrivals": arrivals}


@pytest.fixture
def bench_copy(tmp_path):
    """A copy of the benchmark with cells ``tiny.closed2`` and
    ``tiny.poisson`` added; returns the copy's root."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bdir = tmp_path / "chipbench"
    cfg = json.loads((bdir / "configs" / "yi-6b-8L.json").read_text())
    cfg.update(TINY)
    (bdir / "configs" / "tiny-gqa.json").write_text(json.dumps(cfg))
    for name, arrivals in (("tiny_closed2", {"kind": "closed", "clients": 2}),
                           ("tiny_poisson", {"kind": "poisson",
                                             "rate_per_s": 8.0})):
        (bdir / "traffic" / f"{name}.json").write_text(
            json.dumps(_tiny_traffic(arrivals)))
    for cell, traffic in (("tiny.closed2", "tiny_closed2"),
                          ("tiny.poisson", "tiny_poisson")):
        bench["workloads"].append({"name": cell, "config": "tiny-gqa",
                                   "traffic": traffic, "chips": 1,
                                   "why": "CPU test"})
        (bdir / "checks" / f"{cell}.json").write_text(
            json.dumps({"widest_logit_gap": TINY_LIMIT}))
    for m in bench["per_layer"] + bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"] += ["tiny.closed2", "tiny.poisson"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path
