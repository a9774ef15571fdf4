"""The plain reference against the program, at tiny widths on the CPU.

The reference draws the program's weights from the seed by its own copy
of the recipe, and its float32 forward agrees with the program's forward
run in float32. Then a gap that the output check reads on the chip comes
from the program's arithmetic, not from a different model.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
from conftest import TINY

from chipbench.catalog import BENCH_DIR, load_module
from repro.configs import get_arch
from repro.models import lm

ref = load_module("reference", "dense_gqa")


def _tiny():
    cfg = json.loads((BENCH_DIR / "configs" / "yi-6b-8L.json").read_text())
    # The program's forward fixes the RMS norm's epsilon at 1e-6; the
    # arithmetic is compared at that epsilon.
    cfg.update(TINY, head_dim=32, rms_norm_eps=1e-6)
    pcfg = dataclasses.replace(
        get_arch("yi-6b"), d_model=128, d_ff=256, n_heads=4, n_kv_heads=2,
        n_layers=2, vocab_size=512, head_dim=32)
    return cfg, pcfg


def _same(got, want):
    # The same draws, computed in other fusions: at most a few float32 ulps
    # apart.
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)


def test_reference_draws_the_programs_weights():
    cfg, pcfg = _tiny()
    dm = ref.dims(cfg)
    program = lm.init(pcfg, jax.random.PRNGKey(1234))
    _same(ref.embedding(dm, 1234), program["embed"]["table"])
    _same(ref.head(dm, 1234), program["lm_head"]["w"])
    stacked = program["stage0"]["u0"]
    for layer in range(dm.layers):
        w = ref.layer_weights(dm, 1234, layer)
        for name, got in (("wq", "mixer"), ("wk", "mixer"), ("wv", "mixer"),
                          ("wo", "mixer")):
            _same(w[name], stacked[got][name][layer])
        for name in ("gate", "up", "down"):
            _same(w[name], stacked["ffn"][name]["w"][layer])
        np.testing.assert_array_equal(stacked["norm1"]["scale"][layer], 1.0)


def test_reference_forward_matches_the_program_in_float32():
    cfg, pcfg = _tiny()
    seed, batch, plen, served = 99, 2, 12, 5
    params = lm.init(pcfg, jax.random.PRNGKey(seed))
    prompt = ref.prompt(7, batch, plen, cfg["vocab_size"])
    tokens = jax.random.randint(jax.random.PRNGKey(3), (batch, served), 0,
                                cfg["vocab_size"])
    full = jnp.concatenate([prompt, tokens[:, :-1]], axis=1)
    with jax.default_matmul_precision("highest"):
        want, _, _ = lm.forward(pcfg, params, full, dtype=jnp.float32)
    # Padded to 3 more positions and 1 more row, as the harness pads.
    got = ref.served_logits(
        cfg, seed, [ref.Sequences(7, plen, np.asarray(tokens))],
        seq_len=plen + served + 2, rows=batch + 1)["float32"]
    assert got.shape == (batch + 1, served + 3, cfg["vocab_size"])
    np.testing.assert_allclose(got[:batch, :served], want[:, plen - 1:],
                               rtol=2e-4, atol=2e-5)


def test_control_rounds_below_bfloat16():
    x = jnp.linspace(-3.0, 3.0, 1001)
    err8 = jnp.abs(ref._round(x, "float8") - x).max()
    err16 = jnp.abs(x.astype(jnp.bfloat16).astype(jnp.float32) - x).max()
    assert err8 > 4 * err16
    assert jnp.array_equal(ref._round(x, "float32"), x)
