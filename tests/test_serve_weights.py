"""The served weight set holds matrix weights in the compute dtype.

``serve_params`` rounds each matrix-product weight (the latent attention's
projections, the experts and their router among them) and the embedding
table to bfloat16 once, where the served steps used to round them on every
call.
The steps read those leaves only through ``cast(leaf, compute dtype)``, so
a serve job must compute bit for bit what it computed from the float32 set
that ``lm.init`` draws, in every family whose served step runs here.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS
from repro.core import jobs
from repro.core.executor import LaneExecutor
from repro.core.policies import make_policy
from repro.models import lm
from repro.models.layers import DEFAULT_COMPUTE_DTYPE

WEIGHT_SEED = 11
BATCH, PROMPT, TOKENS_PER_BLOCK, BLOCKS = 3, 8, 2, 2
# Dense GQA first: the path the chip benchmark serves.
FAMILIES = ["yi-6b", "mistral-nemo-12b", "yi-34b", "pixtral-12b",
            "minicpm3-4b", "deepseek-v2-lite-16b", "dbrx-132b",
            "mamba2-2.7b", "recurrentgemma-2b", "whisper-large-v3"]
MATRIX_LEAVES = {"w", "wq", "wk", "wv", "wo", "table",
                 "wdq", "wuq", "wdkv", "wkr", "wuk", "wuv",
                 "gate_w", "up_w", "down_w", "router"}


def _float32_set(cfg):
    """The set the served path held before: ``lm.init`` in one program."""
    return jax.jit(functools.partial(lm.init, cfg))(
        jax.random.PRNGKey(WEIGHT_SEED))


def _run_job(cfg, monkeypatch, params=None):
    """Two blocks of one serve job, on ``params`` in place of the shared
    served set where given."""
    with monkeypatch.context() as m:
        if params is not None:
            m.setattr(jobs, "serve_params", lambda c, s: params)
        job = jobs.make_serve_job(cfg, cfg.arch_id, blocks=BLOCKS,
                                  tokens_per_block=TOKENS_PER_BLOCK,
                                  batch=BATCH, prompt_len=PROMPT, seed=3,
                                  weight_seed=WEIGHT_SEED)
    LaneExecutor([job], make_policy("fifo"), n_lanes=1).run()
    return job


def _logits(cfg, params, prompt):
    """Prefill logits, then the decode logits of each greedy token over
    the two blocks; the prefill is the serve job's own program."""
    max_seq = PROMPT + BLOCKS * TOKENS_PER_BLOCK + 8
    logits, caches = jobs._prefill(cfg, params, prompt, max_seq=max_seq)
    out = [logits]
    decode = jax.jit(functools.partial(lm.decode_step, cfg))
    lengths = jnp.full((BATCH,), PROMPT, jnp.int32)
    for _ in range(BLOCKS * TOKENS_PER_BLOCK):
        logits, caches = decode(params, jnp.argmax(logits, -1), caches,
                                lengths)
        lengths = lengths + 1
        out.append(logits)
    return out


@pytest.mark.parametrize("arch", FAMILIES)
def test_served_set_computes_the_float32_sets_tokens_and_logits(
        arch, monkeypatch):
    cfg = ARCHS[arch].reduced()
    served = jobs.serve_params(cfg, WEIGHT_SEED)
    full = _float32_set(cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(3), (BATCH, PROMPT), 0,
                                cfg.vocab_size)
    for got, want in zip(_logits(cfg, served, prompt),
                         _logits(cfg, full, prompt)):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
    on_served = _run_job(cfg, monkeypatch)
    on_full = _run_job(cfg, monkeypatch, params=full)
    assert on_served.params is served
    assert on_full.params is full
    tokens = on_served.tokens()
    assert tokens.shape == (BATCH, 1 + BLOCKS * TOKENS_PER_BLOCK)
    np.testing.assert_array_equal(tokens, on_full.tokens())


@pytest.mark.parametrize("arch", FAMILIES)
def test_served_set_holds_matrix_weights_in_the_compute_dtype(arch):
    """Matrix weights and the table are the float32 draw rounded once;
    every other leaf, each norm ``scale`` among them, is the float32 draw."""
    cfg = ARCHS[arch].reduced()
    served = jobs.serve_params(cfg, WEIGHT_SEED)
    full = _float32_set(cfg)
    assert served["embed"]["table"].dtype == DEFAULT_COMPUTE_DTYPE
    for (path, leaf), want in zip(
            jax.tree_util.tree_flatten_with_path(served)[0],
            jax.tree.leaves(full)):
        name = path[-1].key
        assert want.dtype == jnp.float32, path
        if name in MATRIX_LEAVES:
            assert leaf.dtype == DEFAULT_COMPUTE_DTYPE, path
            assert jnp.array_equal(leaf, want.astype(leaf.dtype)), path
        else:
            assert leaf.dtype == jnp.float32, path
            assert jnp.array_equal(leaf, want), path


@pytest.mark.parametrize("arch", ["yi-6b", "mistral-nemo-12b",
                                  "deepseek-v2-lite-16b"])
def test_served_steps_convert_no_weight(arch):
    """The lowered prefill and decode on the served set hold no convert
    whose operand has a matrix weight's shape (per layer or stacked)."""
    cfg = ARCHS[arch].reduced()
    served = jobs.serve_params(cfg, WEIGHT_SEED)
    shapes = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(served)[0]:
        if path[-1].key in MATRIX_LEAVES:
            shapes |= {leaf.shape, leaf.shape[1:]}
    max_seq = PROMPT + 8
    prompt = jnp.zeros((BATCH, PROMPT), jnp.int32)
    _, caches = jax.eval_shape(
        lambda p, t: lm.prefill(cfg, p, t, max_seq=max_seq), served, prompt)
    vec = jnp.zeros((BATCH,), jnp.int32)
    texts = [
        jax.jit(lambda p, t: jobs._prefill(cfg, p, t, max_seq=max_seq))
        .lower(served, prompt).as_text(),
        jax.jit(lambda p, t, c, n: jobs._decode(cfg, p, t, c, n))
        .lower(served, vec, caches, vec).as_text(),
    ]
    operands = [tuple(int(d) for d in dims.split("x") if d)
                for text in texts
                for dims in re.findall(
                    r"stablehlo\.convert %\S+ : \(tensor<([0-9x]*)x\w+>\)",
                    text)]
    assert operands, "no convert found: the pattern no longer matches"
    assert not [s for s in operands if s in shapes]
