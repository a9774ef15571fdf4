"""Step kinds: the files that build the program's configuration from a
configuration file's published keys. Adding a step kind adds files under
``steps/``, ``counts/`` and ``reference/`` and entries; it edits none of
the files that are there."""

import dataclasses
import json
import shutil
from pathlib import Path

import pytest
from conftest import TINY_LIMIT
from tinybench import run_tiny

from chipbench import catalog

HERE = Path(__file__).resolve().parent
dense_gqa = catalog.load_module("steps", "dense_gqa")

#: The program's configuration of each benchmark configuration, field for
#: field, as the harness built it before step kinds had files of their own.
PINNED = {
    "yi-6b-8L": {
        "arch_id": "yi-6b", "family": "dense", "vocab_size": 64000,
        "d_model": 4096, "n_layers": 8, "n_heads": 32, "n_kv_heads": 4,
        "d_ff": 11008, "head_dim": None, "norm": "rms", "act": "swiglu",
        "attn_kind": "gqa", "rope_theta": 5000000.0, "mla": None,
        "moe": None, "ssm": None, "rglru": None, "encoder": None,
        "n_patches": 0, "tie_embeddings": False, "sub_quadratic": False,
        "source": "arXiv:2403.04652"},
    "mistral-nemo-12b-4L": {
        "arch_id": "mistral-nemo-12b", "family": "dense",
        "vocab_size": 131072, "d_model": 5120, "n_layers": 4, "n_heads": 32,
        "n_kv_heads": 8, "d_ff": 14336, "head_dim": 128, "norm": "rms",
        "act": "swiglu", "attn_kind": "gqa", "rope_theta": 1000000.0,
        "mla": None, "moe": None, "ssm": None, "rglru": None,
        "encoder": None, "n_patches": 0, "tie_embeddings": False,
        "sub_quadratic": False,
        "source": "hf:mistralai/Mistral-Nemo-Base-2407"},
}

#: DeepSeek-V2-Lite's published keys at ``ArchConfig.reduced()``-like
#: widths: 4 experts of 256, top-2, one shared; a first dense layer of 256.
TINY_MLA_MOE = {
    "name": "tiny-mla-moe", "arch": "deepseek-v2-lite-16b",
    "step_kind": "mla_moe", "hidden_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "vocab_size": 512,
    "intermediate_size": 256, "moe_intermediate_size": 256,
    "kv_lora_rank": 64, "q_lora_rank": None, "qk_nope_head_dim": 32,
    "qk_rope_head_dim": 16, "v_head_dim": 32, "n_routed_experts": 4,
    "num_experts_per_tok": 2, "n_shared_experts": 1,
    "first_k_dense_replace": 1, "rope_theta": 10000, "rms_norm_eps": 1e-06,
    "serving": {"tokens_per_block": 2, "lanes": 1, "policy": "srtf",
                "predictor": "simple-slicing"},
}


def _files(root: Path):
    return {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_dense_gqa_builds_the_program_config_as_pinned(name):
    cell = next(w for w in catalog.load_benchmark()["workloads"]
                if w["config"] == name)
    step = catalog.find_cell(cell["name"]).step()
    pcfg = step.program_config(catalog.load_json("configs", name))
    assert dataclasses.asdict(pcfg) == PINNED[name]


@pytest.mark.parametrize("arch", ["deepseek-v2-lite-16b", "minicpm3-4b",
                                  "dbrx-132b"])
def test_dense_gqa_refuses_latent_attention_and_experts(arch):
    cfg = json.loads((catalog.BENCH_DIR / "configs" / "yi-6b-8L.json")
                     .read_text())
    cfg["arch"] = arch
    with pytest.raises(ValueError, match="dense_gqa step needs"):
        dense_gqa.program_config(cfg)


def _reexport(kind: str) -> str:
    return ("from pathlib import Path\n"
            "from chipbench.catalog import load_module\n"
            f"_base = load_module({kind!r}, 'dense_gqa', "
            "Path(__file__).resolve().parents[1])\n"
            "globals().update({k: v for k, v in vars(_base).items()\n"
            "                  if not k.startswith('__')})\n")


def test_an_added_step_kind_runs_a_cell_with_no_edit(bench_copy):
    """A step kind under a new name, whose steps, counts and reference
    re-export dense_gqa's, with a configuration and a cell of it: all new
    files and entries, and the cell runs end to end, correct."""
    bdir = bench_copy / "chipbench"
    before = _files(bdir)
    for kind in ("steps", "counts", "reference"):
        (bdir / kind / "gqa_again.py").write_text(_reexport(kind))
    cfg = json.loads((bdir / "configs" / "tiny-gqa.json").read_text())
    cfg.update(name="tiny-gqa-again", step_kind="gqa_again")
    (bdir / "configs" / "tiny-gqa-again.json").write_text(json.dumps(cfg))
    (bdir / "checks" / "again.closed2.json").write_text(
        json.dumps({"widest_logit_gap": TINY_LIMIT}))
    bench = json.loads((bench_copy / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "again.closed2",
                               "config": "tiny-gqa-again",
                               "traffic": "tiny_closed2", "chips": 1,
                               "why": "added step kind"})
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = catalog.find_cell("again.closed2", root=bench_copy,
                             bench_dir=bdir)
    assert Path(cell.step().__file__) == bdir / "steps" / "gqa_again.py"
    out = run_tiny(bench_copy, "again.closed2", seconds=1.0)
    assert out.line["correct"] is True
    assert out.line["failed"] == 0 and out.line["attempted"] > 0
    assert out.line["checks"]["widest_logit_gap"]["value"] <= TINY_LIMIT
    assert all(p.read_bytes() == b for p, b in before.items())


def test_a_step_kind_for_latent_attention_and_experts_serves_a_block(
        bench_copy):
    """DeepSeek-V2-Lite's keys at tiny widths, through a step module that
    maps latent attention and experts: the program serves a block of it.
    The plain reference and the published cut are a later step kind's."""
    from repro.core import jobs

    bdir = bench_copy / "chipbench"
    before = _files(bdir)
    shutil.copy(HERE / "mla_moe_step.py", bdir / "steps" / "mla_moe.py")
    (bdir / "configs" / "tiny-mla-moe.json").write_text(
        json.dumps(TINY_MLA_MOE))
    bench = json.loads((bench_copy / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "mla.closed2",
                               "config": "tiny-mla-moe",
                               "traffic": "tiny_closed2", "chips": 1,
                               "why": "added step kind"})
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = catalog.find_cell("mla.closed2", root=bench_copy, bench_dir=bdir)
    pcfg = cell.step().program_config(cell.config)
    assert (pcfg.family, pcfg.attn_kind) == ("moe", "mla")
    assert (pcfg.d_model, pcfg.n_layers, pcfg.n_heads, pcfg.d_ff,
            pcfg.vocab_size) == (128, 2, 4, 256, 512)
    assert dataclasses.asdict(pcfg.mla) == {
        "kv_lora_rank": 64, "q_lora_rank": None, "qk_nope_dim": 32,
        "qk_rope_dim": 16, "v_head_dim": 32}
    assert (pcfg.moe.n_experts, pcfg.moe.top_k, pcfg.moe.n_shared,
            pcfg.moe.first_dense_layers, pcfg.moe.d_ff_dense) == \
        (4, 2, 1, 1, 256)
    with pytest.raises(ValueError, match="needs"):
        dense_gqa.program_config(dict(cell.config, step_kind="dense_gqa"))

    job = jobs.make_serve_job(pcfg, "c1", blocks=1, tokens_per_block=2,
                              batch=2, prompt_len=16, max_residency=1,
                              seed=3, weight_seed=5)
    job.make_block_fn(1)()
    tokens = job.tokens()
    assert tokens.shape == (2, 3)
    assert ((0 <= tokens) & (tokens < 512)).all()
    assert all(p.read_bytes() == b for p, b in before.items())
