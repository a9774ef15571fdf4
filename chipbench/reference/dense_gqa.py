"""Plain reference of a dense GQA decoder, and its lower-precision control.

A Llama-style decoder written from the configuration alone, in
straightforward ``jax.numpy``: token embedding; per layer an RMS norm,
grouped-query attention with rotary embeddings (halves rotated) under a
causal mask, an RMS norm and a SwiGLU MLP, each added to the residual
stream; a final RMS norm and an untied head. It imports nothing of the
program under test and takes nothing it made.

Weights. The program draws its weights from the seed by a fixed recipe,
and the reference draws the same ones from the same seed by its own copy
of that recipe (``_keys``, ``layer_weights``): float32 normal draws, each
scaled by the inverse square root of its fan-in (the embedding and the
head by 0.02), norm scales of one. A program may store them in any type;
it has to round from these draws.

Precision. ``"float32"`` computes every product in float32 at the highest
matmul precision; it is the reference. ``"float8"`` rounds both operands of
every product (weights, activations, attention scores and probabilities)
to float8 e4m3 and accumulates in float32: the control, one precision below
the bfloat16 that the configuration states.

Memory. The layers are drawn one at a time and applied to every sequence
before the next is drawn; attention runs over blocks of rows small enough
that a block's scores fit in about two gigabytes. Every call of a cell
has one shape, so that it compiles once.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: Bytes of attention scores that one block of rows may hold.
SCORE_BYTES = 2 << 30


class Dims(NamedTuple):
    d: int
    heads: int
    kv: int
    hd: int
    ff: int
    vocab: int
    layers: int
    theta: float
    eps: float


def dims(cfg: Dict) -> Dims:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return Dims(d, h, cfg["num_key_value_heads"],
                cfg.get("head_dim") or d // h,
                cfg["intermediate_size"], cfg["vocab_size"],
                cfg["num_hidden_layers"], float(cfg["rope_theta"]),
                float(cfg["rms_norm_eps"]))


# ------------------------------------------------------------ the weights
def _keys(seed: int):
    return jax.random.split(jax.random.PRNGKey(seed), 5)


@functools.partial(jax.jit, static_argnums=0)
def embedding(dm: Dims, seed: int):
    return jax.random.normal(_keys(seed)[0], (dm.vocab, dm.d),
                             jnp.float32) * 0.02


@functools.partial(jax.jit, static_argnums=0)
def head(dm: Dims, seed: int):
    return jax.random.normal(_keys(seed)[1], (dm.d, dm.vocab)) * 0.02


@functools.partial(jax.jit, static_argnums=0)
def layer_weights(dm: Dims, seed: int, layer: int) -> Dict:
    key = jax.random.split(jax.random.fold_in(_keys(seed)[3], 0),
                           dm.layers)[layer]
    ks = jax.random.split(key, 6)
    ka = jax.random.split(ks[0], 4)
    km = jax.random.split(ks[2], 3)
    s_in, s_out = 1.0 / math.sqrt(dm.d), 1.0 / math.sqrt(dm.heads * dm.hd)

    def normal(k, shape, scale):
        return jax.random.normal(k, shape, jnp.float32) * scale

    return {
        "wq": normal(ka[0], (dm.d, dm.heads, dm.hd), s_in),
        "wk": normal(ka[1], (dm.d, dm.kv, dm.hd), s_in),
        "wv": normal(ka[2], (dm.d, dm.kv, dm.hd), s_in),
        "wo": normal(ka[3], (dm.heads, dm.hd, dm.d), s_out),
        "gate": normal(km[0], (dm.d, dm.ff), s_in),
        "up": normal(km[1], (dm.d, dm.ff), s_in),
        "down": normal(km[2], (dm.ff, dm.d), 1.0 / math.sqrt(dm.ff)),
    }


def prompt(seed: int, batch: int, prompt_len: int, vocab: int) -> jnp.ndarray:
    """A job's prompt, drawn from its own seed."""
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, prompt_len),
                              0, vocab)


# ------------------------------------------------------------ the forward
def _round(a, precision: str):
    if precision == "float8":
        return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return a


def _ein(spec: str, a, b, precision: str):
    return jnp.einsum(spec, _round(a, precision), _round(b, precision),
                      precision=HIGHEST, preferred_element_type=jnp.float32)


def _rms(x, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta: float):
    """x: [r, S, heads, hd]; rotates the two halves of each head."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(dm: Dims, w: Dict, x, precision: str):
    """One layer over x: [r, S, d] float32."""
    S = x.shape[1]
    h = _rms(x, dm.eps)
    q = _rope(_ein("rsd,dhk->rshk", h, w["wq"], precision), dm.theta)
    k = _rope(_ein("rsd,dhk->rshk", h, w["wk"], precision), dm.theta)
    v = _ein("rsd,dhk->rshk", h, w["wv"], precision)
    g = dm.heads // dm.kv
    q = q.reshape(q.shape[0], S, dm.kv, g, dm.hd) / math.sqrt(dm.hd)
    scores = _ein("rqkgd,rskd->rkgqs", q, k, precision)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = _ein("rkgqs,rskd->rqkgd", probs, v, precision)
    o = o.reshape(o.shape[0], S, dm.heads, dm.hd)
    x = x + _ein("rshk,hkd->rsd", o, w["wo"], precision)
    h = _rms(x, dm.eps)
    a = jax.nn.silu(_ein("rsd,df->rsf", h, w["gate"], precision))
    a = a * _ein("rsd,df->rsf", h, w["up"], precision)
    return x + _ein("rsf,fd->rsd", a, w["down"], precision)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _logits(dm: Dims, w_head, x, start: int, precision: str):
    """Logits of the positions from ``start`` on: [r, S - start, vocab]."""
    h = _rms(x[:, start:], dm.eps)
    return _ein("rsd,dv->rsv", h, w_head, precision)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _embed(dm: Dims, table, tokens, precision: str):
    return _round(table, precision)[tokens]


class Sequences(NamedTuple):
    """One job as the reference sees it: its prompt's seed and shape, and
    the tokens it served, ``[batch, n]`` (the first from the prefill)."""

    prompt_seed: int
    prompt_len: int
    served: np.ndarray


def _row_block(dm: Dims, rows: int, S: int) -> int:
    """Rows to a call: as many as keep a call's scores within the budget,
    spread evenly over the calls."""
    most = max(1, SCORE_BYTES // (4 * dm.heads * S * S))
    calls = -(-rows // most)
    return -(-rows // calls)


def served_logits(cfg: Dict, weight_seed: int, jobs: List[Sequences], *,
                  seq_len: int, rows: int,
                  precisions: Tuple[str, ...] = ("float32",),
                  ) -> Dict[str, jnp.ndarray]:
    """Logits ``[rows, seq_len - prompt_len + 1, vocab]`` at the positions
    where the jobs' served tokens were chosen, for each precision. Each
    row is a sequence of a job: its prompt followed by its served tokens
    but the last, as the program fed them. Every call has the same shape:
    sequences are padded at the end to ``seq_len`` (causal attention keeps
    the padding out of the positions before it) and the rows to ``rows``
    with sequences of token 0; what lies past a job's tokens is to be left
    out by the caller."""
    dm = dims(cfg)
    prompt_len = jobs[0].prompt_len
    seqs = []
    for job in jobs:
        batch, n = job.served.shape
        toks = jnp.concatenate(
            [prompt(job.prompt_seed, batch, prompt_len, dm.vocab),
             jnp.asarray(job.served[:, :-1], jnp.int32)], axis=1)
        seqs.append(jnp.pad(toks, ((0, 0), (0, seq_len - toks.shape[1]))))
    tokens = jnp.concatenate(seqs, axis=0)
    tokens = jnp.pad(tokens, ((0, rows - tokens.shape[0]), (0, 0)))
    table = embedding(dm, weight_seed)
    xs = {p: _embed(dm, table, tokens, p) for p in precisions}
    del table
    block = _row_block(dm, rows, seq_len)
    pad = -(-rows // block) * block - rows
    xs = {p: jnp.pad(x, ((0, pad), (0, 0), (0, 0))) for p, x in xs.items()}
    for layer in range(dm.layers):
        w = layer_weights(dm, weight_seed, layer)
        for p, x in xs.items():
            xs[p] = jnp.concatenate(
                [_layer(dm, w, x[r:r + block], p)
                 for r in range(0, x.shape[0], block)], axis=0)
        del w
    w_head = head(dm, weight_seed)
    return {p: _logits(dm, w_head, x[:rows], prompt_len - 1, p)
            for p, x in xs.items()}
