"""The ``deepseek_v2`` step kind (latent attention and experts): the
program against the plain reference (``reference/deepseek_v2.py``) at
small widths on the CPU, on seeded random weights, and the counts against
figures worked out by hand from the published shapes."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tinybench import run_tiny

from chipbench import catalog
from repro.core import jobs
from repro.models import lm

step = catalog.load_module("steps", "deepseek_v2")
ref = catalog.load_module("reference", "deepseek_v2")
counts = catalog.load_module("counts", "deepseek_v2")

#: DeepSeek-V2-Lite's published keys at small widths: one dense layer and
#: two expert layers of 8 routed experts, top-3, beside 2 shared; YaRN as
#: published.
TINY = {
    "name": "tiny-mla-moe", "arch": "deepseek-v2-lite-16b",
    "step_kind": "deepseek_v2", "hidden_size": 128, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "vocab_size": 512,
    "intermediate_size": 256, "moe_intermediate_size": 64,
    "kv_lora_rank": 64, "q_lora_rank": None, "qk_nope_head_dim": 32,
    "qk_rope_head_dim": 16, "v_head_dim": 32, "n_routed_experts": 8,
    "num_experts_per_tok": 3, "n_shared_experts": 2,
    "first_k_dense_replace": 1, "norm_topk_prob": False,
    "routed_scaling_factor": 1.0, "scoring_func": "softmax",
    "topk_method": "greedy", "rope_theta": 10000, "rms_norm_eps": 1e-06,
    "rope_scaling": {"type": "yarn", "factor": 40,
                     "original_max_position_embeddings": 4096,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                     "mscale_all_dim": 0.707},
    "serving": {"tokens_per_block": 4, "lanes": 1, "policy": "srtf",
                "predictor": "simple-slicing"},
}
#: Largest gap between the float32 program and the reference: the same
#: equations in float32 at the highest precision differ by rounding alone
#: (1.4e-6 measured at these widths).
EXACT = 1e-4
#: Widest logit gap allowed for the served bfloat16 program at these
#: widths. Over ten weight seeds on the CPU the program read 0 to 0.028
#: (bfloat16 products, routing by bfloat16 router logits), the float8
#: control 0.109 to 0.278.
TINY_LIMIT = 0.06
BATCH, PROMPT, STEPS = 4, 16, 10


def _cfg(**changes):
    return dict(TINY, **changes)


def _prompt(seed):
    return jax.random.randint(jax.random.PRNGKey(seed), (BATCH, PROMPT), 0,
                              TINY["vocab_size"])


def _served(cfg, weight_seed, prompt):
    """The served path's prefill, then ``STEPS`` greedy decode steps through
    the latent cache: (logits [B, STEPS + 1, V], tokens [B, STEPS + 1])."""
    pcfg = step.program_config(cfg)
    params = jobs.serve_params(pcfg, weight_seed)
    logits, caches = jobs._prefill(pcfg, params, prompt,
                                   max_seq=PROMPT + STEPS + 8)
    out, toks = [logits], [jnp.argmax(logits, -1)]
    lengths = jnp.full((BATCH,), PROMPT, jnp.int32)
    decode = jax.jit(lambda p, t, c, n: lm.decode_step(pcfg, p, t, c, n))
    for _ in range(STEPS):
        logits, caches = decode(params, toks[-1], caches, lengths)
        lengths = lengths + 1
        out.append(logits)
        toks.append(jnp.argmax(logits, -1))
    return (np.stack([np.asarray(o, np.float32) for o in out], 1),
            np.stack([np.asarray(t) for t in toks], 1))


def _float32_program(cfg, params, prompt, tokens):
    """Prefill and decode of the program in float32 at the highest
    precision, fed ``tokens``: logits [B, STEPS + 1, V]."""
    pcfg = step.program_config(cfg)
    with jax.default_matmul_precision("highest"):
        logits, caches = lm.prefill(pcfg, params, prompt,
                                    max_seq=PROMPT + STEPS + 8,
                                    dtype=jnp.float32)
        out = [logits]
        lengths = jnp.full((BATCH,), PROMPT, jnp.int32)
        for i in range(STEPS):
            logits, caches = lm.decode_step(pcfg, params, tokens[:, i],
                                            caches, lengths,
                                            dtype=jnp.float32)
            lengths = lengths + 1
            out.append(logits)
    return np.stack([np.asarray(o) for o in out], 1)


def _reference(cfg, weight_seed, prompt, tokens,
               precisions=("float32",)):
    seq = jnp.concatenate([prompt, jnp.asarray(tokens[:, :-1])], axis=1)
    out = ref.forward(cfg, weight_seed, seq, start=PROMPT - 1,
                      precisions=precisions)
    return {p: np.asarray(v) for p, v in out.items()}


def _widest_gap(logits, chosen):
    best = logits.max(-1)
    got = np.take_along_axis(logits, chosen[..., None], -1)[..., 0]
    return float((best - got).max())


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_prefill_and_decode_through_the_latent_cache_match_the_reference(
        norm_topk_prob):
    """The float32 program equals the reference to rounding; the served
    bfloat16 program's tokens lie within the limit of the reference's best,
    and the float8 control's do not."""
    cfg = _cfg(norm_topk_prob=norm_topk_prob)
    seed, prompt = 5, _prompt(3)
    served_logits, tokens = _served(cfg, seed, prompt)
    want = _reference(cfg, seed, prompt, tokens, ("float32", "float8"))
    params = lm.init(step.program_config(cfg), jax.random.PRNGKey(seed))
    got = _float32_program(cfg, params, prompt, tokens)
    np.testing.assert_allclose(got, want["float32"], rtol=0, atol=EXACT)
    assert _widest_gap(want["float32"], tokens) <= TINY_LIMIT
    control = want["float8"].argmax(-1)
    assert _widest_gap(want["float32"], control) > TINY_LIMIT


def test_the_gates_follow_norm_topk_prob():
    """Renormalised top-k gates are another model: the reference moves by
    far more than rounding when the flag turns, and the program with it."""
    seed, prompt = 5, _prompt(3)
    _, tokens = _served(TINY, seed, prompt)
    plain = _reference(TINY, seed, prompt, tokens)["float32"]
    normed = _reference(_cfg(norm_topk_prob=True), seed, prompt,
                        tokens)["float32"]
    assert np.abs(plain - normed).max() > 100 * EXACT
    params = lm.init(step.program_config(TINY), jax.random.PRNGKey(seed))
    np.testing.assert_allclose(
        _float32_program(_cfg(norm_topk_prob=True), params, prompt, tokens),
        normed, rtol=0, atol=EXACT)


def test_a_router_sending_every_token_to_the_same_experts_drops_none(
        monkeypatch):
    """With every router weight zero, all scores tie and every token goes
    to experts 0-2 (top-3 takes the lowest indices): one expert holds all
    the batch's tokens, far past any capacity, and the program still
    computes each of them, as the reference does."""
    seed, prompt = 7, _prompt(4)
    pcfg = step.program_config(TINY)
    params = lm.init(pcfg, jax.random.PRNGKey(seed))
    ffn = params["stage1"]["u0"]["ffn"]
    ffn["router"] = jnp.zeros_like(ffn["router"])
    layer_weights = ref.layer_weights

    def zero_router(dm, weight_seed, layer):
        w = layer_weights(dm, weight_seed, layer)
        if "router" in w:
            w = dict(w, router=jnp.zeros_like(w["router"]))
        return w

    monkeypatch.setattr(ref, "layer_weights", zero_router)
    tokens = np.zeros((BATCH, STEPS + 1), np.int32)
    tokens[:, 1:] = np.arange(STEPS) % TINY["vocab_size"]
    want = _reference(TINY, seed, prompt, tokens)["float32"]
    got = _float32_program(TINY, params, prompt, tokens)
    np.testing.assert_allclose(got, want, rtol=0, atol=EXACT)


def test_a_tiny_cell_runs_correct_and_its_control_does_not(bench_copy):
    """A cell of the step kind at small widths, through the harness: the
    served path, the output check against the reference, the control."""
    bdir = bench_copy / "chipbench"
    (bdir / "configs" / "tiny-mla-moe.json").write_text(json.dumps(TINY))
    (bdir / "checks" / "mla.closed2.json").write_text(
        json.dumps({"widest_logit_gap": TINY_LIMIT}))
    bench = json.loads((bench_copy / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "mla.closed2",
                               "config": "tiny-mla-moe",
                               "traffic": "tiny_closed2", "chips": 1,
                               "why": "CPU test"})
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(bench))
    out = run_tiny(bench_copy, "mla.closed2", seconds=1.0)
    assert out.line["correct"] is True, out.line["checks"]
    assert out.line["failed"] == 0 and out.line["attempted"] > 0
    control = run_tiny(bench_copy, "mla.closed2", seconds=0.5, control=True)
    assert control.check["program_widest_logit_gap"] <= TINY_LIMIT
    assert control.line["correct"] is False


# ------------------------------------------------------------ the step
def _published():
    return catalog.load_json("configs", "deepseek-v2-lite-5L")


def test_the_step_maps_the_published_cut():
    pcfg = step.program_config(_published())
    assert (pcfg.d_model, pcfg.n_layers, pcfg.n_heads, pcfg.d_ff,
            pcfg.vocab_size) == (2048, 5, 16, 1408, 102400)
    assert (pcfg.mla.kv_lora_rank, pcfg.mla.q_lora_rank, pcfg.mla.qk_nope_dim,
            pcfg.mla.qk_rope_dim, pcfg.mla.v_head_dim) == (512, None, 128, 64,
                                                          128)
    rs = pcfg.rope_scaling
    assert (rs.factor, rs.original_max_position, rs.beta_fast, rs.beta_slow,
            rs.mscale, rs.mscale_all_dim) == (40, 4096, 32, 1, 0.707, 0.707)
    moe = pcfg.moe
    assert (moe.n_experts, moe.top_k, moe.n_shared, moe.first_dense_layers,
            moe.d_ff_dense, moe.norm_topk_prob,
            moe.routed_scaling_factor) == (64, 6, 2, 1, 10944, False, 1.0)


@pytest.mark.parametrize("change", [
    {"arch": "yi-6b"},
    {"scoring_func": "sigmoid"},
    {"topk_method": "group_limited_greedy", "n_group": 8, "topk_group": 3},
    {"q_lora_rank": 1536},
    {"rope_scaling": {"type": "linear", "factor": 4}},
    {"tie_word_embeddings": True},
])
def test_the_step_refuses_other_structures(change):
    with pytest.raises(ValueError):
        step.program_config(dict(_published(), **change))


@pytest.mark.parametrize("scaling,factor", [
    (None, None),
    ({"type": "yarn", "factor": 8, "original_max_position_embeddings": 4096,
      "beta_fast": 32, "beta_slow": 1, "mscale": 1.0,
      "mscale_all_dim": 0.0}, 8.0),
])
def test_the_file_s_rope_scaling_is_the_program_s(scaling, factor):
    """The program takes the file's rotary scaling, whatever its ``arch``
    publishes: none, or YaRN of another factor."""
    pcfg = step.program_config(dict(_published(), rope_scaling=scaling))
    got = pcfg.rope_scaling
    assert (got is None) if factor is None else (got.factor == factor)


# ----------------------------------------------------------- the counts
def test_counts_reproduce_the_published_cut():
    cfg = _published()
    # Attention: q 2048x16x192, latent 2048x512 and its norm, rotary key
    # 2048x64, up-projections 512x16x(128+128), output 16x128x2048.
    attn = (2048 * 16 * 192 + 2048 * 512 + 512 + 2048 * 64
            + 512 * 16 * 256 + 16 * 128 * 2048)
    assert counts.attention_params(cfg) == attn == 13_763_072
    # Router, 64 routed and 2 shared SwiGLUs of 1408.
    assert counts.moe_layer_params(cfg) == 2048 * 64 + 66 * 3 * 2048 * 1408
    total = (2 * 102400 * 2048 + 2048 + 5 * (attn + 2 * 2048)
             + 3 * 2048 * 10944 + 4 * counts.moe_layer_params(cfg))
    assert counts.params(cfg) == total == 2_839_831_040
    norms = 2048 + 5 * (2 * 2048 + 512)
    served = 2 * (total - norms) + 4 * norms
    assert served / 2**30 == pytest.approx(5.29, abs=0.005)


def test_decode_reads_the_touched_experts():
    """At batch 8, top-6 of 64: about 34.9 experts touched; the expert
    layers' bytes lie between the touched experts' and all 64's."""
    cfg = _published()
    touched = counts.touched_experts(cfg, 8)
    assert touched == pytest.approx(64 * (1 - (58 / 64) ** 8))
    assert touched == pytest.approx(34.88, abs=0.01)
    expert = 3 * 2048 * 1408 * 2
    flops, nbytes = counts.moe_decode(cfg, batch=8)
    assert 4 * touched * expert < nbytes < 4 * 64 * expert
    assert 4 * touched * expert == pytest.approx(2.42e9, rel=0.01)
    assert flops == 4 * 8 * (2 * 2048 * 64 + 8 * 2 * 3 * 2048 * 1408)
    step_flops, step_bytes = counts.decode_step(cfg, batch=8, filled=512)
    assert step_bytes == pytest.approx(3.27e9, rel=0.01)
    assert 0.74 < nbytes / step_bytes < 0.84
    # Latent attention, absorbed: 513 positions of 576 values, 16 heads.
    mla_flops, mla_bytes = counts.mla_decode(cfg, batch=8, filled=512)
    matmul = counts.attention_params(cfg) - 512
    assert mla_flops == 5 * 8 * (2 * matmul + 2 * 16 * (2 * 512 + 64) * 513)
    assert mla_bytes == 2 * 5 * (counts.attention_params(cfg) + 2 * 8 * 2048
                                 + 8 * 576 * 513)


def test_prefill_counts_every_expert_and_the_routed_half():
    cfg = _published()
    flops, nbytes = counts.prefill(cfg, batch=8, prompt_len=512)
    moe_flops, moe_bytes = counts.moe_prefill(cfg, batch=8, prompt_len=512)
    routed = 4 * 4096 * 6 * 2 * 3 * 2048 * 1408
    assert routed == pytest.approx(1.70e12, rel=0.01)
    assert flops == pytest.approx(3.44e12, rel=0.01)
    assert moe_bytes > 4 * 64 * 3 * 2048 * 1408 * 2 * 0.999
    assert 0.45 < routed / flops < 0.55
    assert moe_flops < flops and moe_bytes < nbytes


# ----------------------------------------------------------- YaRN, scale
def test_the_reference_yarn_matches_hand_values():
    """DeepSeek-V2-Lite's YaRN: rotary dimension 64, base 1e4, factor 40
    over 4096 positions, beta 32 and 1: the correction range is dimensions
    10 to 23 (floor 10.47, ceil 22.51); mscale 0.1 x 0.707 x ln 40 + 1."""
    dm = ref.dims(_published())
    inv = ref.rotary_frequencies(dm)
    base = 1e4 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    ramp = (16 - 10) / 13
    assert inv[16] == pytest.approx(base[16] * (1 - ramp)
                                    + base[16] / 40 * ramp, rel=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert ref.softmax_scale(dm) == pytest.approx(192 ** -0.5 * m * m)
