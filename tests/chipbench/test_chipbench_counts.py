"""Operation and byte counts of the served steps, against values worked
out by hand from the published shapes."""

import json

from chipbench.catalog import BENCH_DIR, load_module

counts = load_module("counts", "dense_gqa")


def _cfg(name):
    return json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())


def test_layer_parameters():
    # yi-6b: q 4096x32x128, k and v 4096x4x128, o 32x128x4096,
    # gate/up/down 4096x11008, two norms of 4096.
    assert counts.layer_params(_cfg("yi-6b-8L")) == (
        4096 * 128 * 40 + 4096 * 4096 + 3 * 4096 * 11008 + 2 * 4096
    ) == 173_023_232
    # mistral-nemo: head_dim 128 with 32 query and 8 KV heads over 5120.
    assert counts.layer_params(_cfg("mistral-nemo-12b-4L")) == 272_640_000


def test_yi_decode_step():
    flops, nbytes = counts.decode_step(_cfg("yi-6b-8L"), batch=8, filled=512)
    # 8 layers x 8 rows x (2 x 173,015,040 matmul weights + 4 x 32 heads
    # x 128 x 513 attended) + the head, 2 x 8 x 4096 x 64000.
    assert flops == 8 * 8 * (2 * 173_015_040 + 4 * 32 * 128 * 513) \
        + 2 * 8 * 4096 * 64000 == 26_878_148_608
    # bf16 weights (8 layers, head, final norm, 8 embedding rows) and the
    # KV cache of 513 positions, 2 x 4 x 128 values a layer and row.
    weights = 8 * 173_023_232 + 4096 * 64000 + 4096 + 8 * 4096
    cache = 8 * 8 * 1024 * 513
    assert nbytes == 2 * (weights + cache) == 3_359_973_376


def test_nemo_decode_step_and_prefill():
    cfg = _cfg("mistral-nemo-12b-4L")
    flops, nbytes = counts.decode_step(cfg, batch=8, filled=600)
    assert flops == 4 * 8 * (2 * 272_629_760 + 4 * 32 * 128 * 601) \
        + 2 * 8 * 5120 * 131072
    assert nbytes == 2 * (4 * 272_640_000 + 5120 * 131072 + 5120
                          + 8 * 5120 + 4 * 8 * 2048 * 601)
    flops, nbytes = counts.prefill(cfg, batch=8, prompt_len=512)
    assert flops == 4 * (2 * 272_629_760 * 4096
                         + 4 * 32 * 128 * 8 * 512 * 513 // 2) \
        + 2 * 8 * 5120 * 131072
    assert nbytes == 2 * (4 * 272_640_000 + 5120 * 131072 + 5120
                          + 4096 * 5120 + 4 * 4096 * 2048)


def test_yi_prefill_is_compute_bound_on_v5e():
    flops, nbytes = counts.prefill(_cfg("yi-6b-8L"), batch=8, prompt_len=512)
    # 4096 tokens through the matmuls, 8 x 512 x 513 / 2 causal pairs.
    assert flops == 8 * (2 * 173_015_040 * 4096
                         + 4 * 32 * 128 * 8 * 512 * 513 // 2) \
        + 2 * 8 * 4096 * 64000 == 11_480_615_354_368
    peaks = json.loads((BENCH_DIR / "peaks.json").read_text())
    v5e = peaks["devices"]["TPU v5 lite"]
    assert flops / v5e["bf16_flops_per_s"] > nbytes / v5e["hbm_bytes_per_s"]
