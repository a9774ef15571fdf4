"""Latent attention with YaRN and the drop-free expert layer
(DeepSeek-V2-Lite): the rotary frequencies and softmax factor against
values worked out by hand from the published formulas, the gates against
the configuration's flags, and the named scopes that a profile times them
by."""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core import jobs
from repro.kernels import ref
from repro.models import mla, moe
from repro.models.layers import apply_rope_pairs, yarn_mscale, yarn_rope_freqs

DSV2 = ARCHS["deepseek-v2-lite-16b"]


def test_yarn_frequencies_and_softmax_factor_by_hand():
    """Rotary dimension 64, base 1e4, factor 40 over 4096 positions, beta
    32 and 1: dimensions below 10 keep their frequency (the correction
    range's low end is floor(64 ln(4096 / (32 2 pi)) / (2 ln 1e4)) =
    floor(10.47)), those from 23 on (ceil(22.51)) are divided by 40, a
    linear ramp between; the softmax scale gains (0.1 x 0.707 x ln 40 +
    1) ** 2 = 1.5896, the cos and sin none (mscale = mscale_all_dim)."""
    m, scaling = DSV2.mla, DSV2.rope_scaling
    inv = yarn_rope_freqs(64, 10_000.0, scaling)
    base = 10_000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    for i in range(11, 23):
        ramp = (i - 10) / 13
        assert inv[i] == pytest.approx(
            base[i] * (1 - ramp) + base[i] / 40 * ramp, rel=1e-6)
    assert yarn_mscale(40, 0.707) == pytest.approx(1.260804, abs=1e-6)
    rot = mla._rotary(m, DSV2.rope_theta, scaling)
    assert rot.softmax_scale == pytest.approx(192 ** -0.5 * 1.260804 ** 2,
                                              rel=1e-6)
    assert rot.cos_sin_scale == 1.0
    plain = mla._rotary(m, 1e4, None)
    assert plain.softmax_scale == 192 ** -0.5
    np.testing.assert_allclose(plain.inv_freq, base, rtol=1e-6)


def test_rope_pairs_turn_interleaved_pairs():
    """Pair (x[2i], x[2i+1]) turns by position x inv_freq[i]; the result
    lists the first members, then the second."""
    x = jnp.arange(8, dtype=jnp.float32).reshape(1, 1, 1, 8) + 1
    inv = np.array([1.0, 0.5, 0.25, 0.125], np.float32)
    got = np.asarray(apply_rope_pairs(x, jnp.array([[3]]), inv, 2.0))[0, 0, 0]
    for i in range(4):
        a, b = float(x[0, 0, 0, 2 * i]), float(x[0, 0, 0, 2 * i + 1])
        c, s = 2 * math.cos(3 * inv[i]), 2 * math.sin(3 * inv[i])
        assert got[i] == pytest.approx(a * c - b * s, rel=1e-5)
        assert got[4 + i] == pytest.approx(b * c + a * s, rel=1e-5)


@pytest.mark.parametrize("norm_topk_prob, scale", [(True, 1.0),
                                                   (False, 1.0),
                                                   (False, 2.5)])
def test_moe_gates_follow_the_configuration(norm_topk_prob, scale):
    """The expert layer's routed part is the top-k softmax scores,
    renormalised where ``norm_topk_prob``, else times the routed scale."""
    cfg = dataclasses.replace(
        DSV2.reduced().moe, n_shared=0, norm_topk_prob=norm_topk_prob,
        routed_scaling_factor=scale)
    p = moe.moe_init(jax.random.PRNGKey(1), 32, 16, cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 32))
    with jax.default_matmul_precision("highest"):
        got, _ = moe.moe_apply(p, x, cfg, dtype=jnp.float32)
        probs = jax.nn.softmax(x @ p["router"], -1).reshape(16, -1)
    gate, idx = jax.lax.top_k(probs, cfg.top_k)
    gate = (gate / gate.sum(-1, keepdims=True) if norm_topk_prob
            else gate * scale)
    dense = jnp.zeros_like(probs).at[jnp.arange(16)[:, None], idx].set(gate)
    want = ref.moe_dense(x.reshape(16, 32), p["gate_w"], p["up_w"],
                         p["down_w"], dense)
    np.testing.assert_allclose(np.asarray(got).reshape(16, 32),
                               np.asarray(want), rtol=1e-4, atol=1e-5)


def _scopes(text):
    """The path segments of a compiled program's HLO ``op_name``s."""
    return {seg for name in re.findall(r'op_name="([^"]*)"', text)
            for seg in name.split("/")}


@pytest.mark.parametrize("arch, scopes", [
    ("deepseek-v2-lite-16b", {"mla", "moe"}), ("yi-6b", set())])
def test_only_latent_attention_and_experts_open_named_scopes(arch, scopes):
    """A compiled prefill and decode step hold the ``mla`` and ``moe``
    segments where the model has latent attention and experts; a dense
    model's steps hold neither."""
    cfg = ARCHS[arch].reduced()
    params = jobs.serve_params(cfg, 0)
    prompt = jnp.zeros((2, 8), jnp.int32)
    _, caches = jax.eval_shape(
        lambda p, t: jobs._prefill(cfg, p, t, max_seq=16), params, prompt)
    vec = jnp.zeros((2,), jnp.int32)
    for text in (
            jobs._prefill.lower(cfg, params, prompt, max_seq=16)
            .compile().as_text(),
            jobs._decode.lower(cfg, params, vec, caches, vec)
            .compile().as_text()):
        assert _scopes(text) & {"mla", "moe"} == scopes
