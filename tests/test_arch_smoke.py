"""Per-architecture smoke tests: reduced configs, one forward/train step on
CPU, shape + finiteness assertions, and prefill/decode cache consistency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.configs.shapes import all_cells
from repro.models import lm

ARCH_IDS = sorted(ARCHS)


def make_batch(cfg, key, B=2, S=32):
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    if cfg.n_patches:
        batch["patches"] = 0.02 * jax.random.normal(
            key, (B, cfg.n_patches, cfg.d_model))
    if cfg.encoder:
        batch["frames"] = 0.02 * jax.random.normal(
            key, (B, cfg.encoder.n_frames, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_forward_shapes_and_finite(arch_id):
    cfg = ARCHS[arch_id].reduced()
    key = jax.random.PRNGKey(0)
    params = lm.init(cfg, key)
    batch = make_batch(cfg, key)
    logits, aux, _ = lm.forward(cfg, params, batch["tokens"],
                                patches=batch.get("patches"),
                                enc_frames=batch.get("frames"))
    B, S = batch["tokens"].shape
    prefix = cfg.n_patches or 0
    assert logits.shape == (B, S + prefix, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_train_step_reduces_loss(arch_id):
    """One SGD step on a repeated batch must reduce the loss."""
    cfg = ARCHS[arch_id].reduced()
    key = jax.random.PRNGKey(1)
    params = lm.init(cfg, key)
    batch = make_batch(cfg, key)

    def loss(p):
        return lm.loss_fn(cfg, p, batch)[0]

    l0, grads = jax.value_and_grad(loss)(params)
    assert np.isfinite(float(l0))
    gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                         for g in jax.tree.leaves(grads)))
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0
    lr = 0.1 / max(float(gnorm), 1.0)
    p2 = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype), params, grads)
    l1 = loss(p2)
    assert float(l1) < float(l0)


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_prefill_decode_matches_forward(arch_id):
    """decode_step on a prefilled cache must reproduce forward() logits."""
    cfg = ARCHS[arch_id].reduced()
    key = jax.random.PRNGKey(2)
    params = lm.init(cfg, key)
    B, S = 2, 16
    batch = make_batch(cfg, key, B=B, S=S)
    tokens = batch["tokens"]

    # Full forward over S tokens: logits at position S-1 predict token S.
    logits_all, _, _ = lm.forward(cfg, params, tokens,
                                  patches=batch.get("patches"),
                                  enc_frames=batch.get("frames"))
    # Prefill on the first S-1 tokens, then decode token S-1.
    prefix = cfg.n_patches or 0
    last, caches = lm.prefill(cfg, params, tokens[:, : S - 1],
                              max_seq=S + prefix + 4,
                              patches=batch.get("patches"),
                              enc_frames=batch.get("frames"))
    lengths = jnp.full((B,), S - 1 + prefix, jnp.int32)
    dec_logits, _ = lm.decode_step(cfg, params, tokens[:, S - 1], caches,
                                   lengths)
    want = np.asarray(logits_all[:, -1, :], np.float32)
    got = np.asarray(dec_logits, np.float32)
    np.testing.assert_allclose(got, want, rtol=0.08, atol=0.08)


def test_cell_matrix_counts():
    cells = all_cells()
    assert len(cells) == 40  # 10 archs x 4 shapes
    runnable = [c for c in cells if c[2]]
    skipped = [c for c in cells if not c[2]]
    # long_500k runs only for the sub-quadratic archs
    assert len(skipped) == 8
    assert all(c[1] == "long_500k" for c in skipped)
    assert {c[0] for c in cells if c[1] == "long_500k" and c[2]} == \
        {"mamba2-2.7b", "recurrentgemma-2b"}
    assert len(runnable) == 32


def test_param_counts_match_published_sizes():
    expected = {
        "mamba2-2.7b": 2.7e9, "dbrx-132b": 132e9,
        "deepseek-v2-lite-16b": 16e9, "pixtral-12b": 12e9,
        "yi-34b": 34e9, "mistral-nemo-12b": 12e9, "yi-6b": 6e9,
        "minicpm3-4b": 4e9, "recurrentgemma-2b": 2.7e9,
    }
    for arch_id, want in expected.items():
        got = ARCHS[arch_id].n_params()
        assert 0.75 * want < got < 1.35 * want, (arch_id, got, want)
