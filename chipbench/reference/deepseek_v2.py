"""Plain reference of a decoder with latent attention and a mixture of
experts (DeepSeek-V2's structure), and its lower-precision control.

Written from the configuration alone, after DeepSeek-V2's published
equations (arXiv:2405.04434 and its ``modeling_deepseek.py``), in
straightforward ``jax.numpy``: token embedding; per layer an RMS norm,
latent attention in its expanded form with no cache, an RMS norm and
either a dense SwiGLU MLP (the first ``first_k_dense_replace`` layers) or
the expert layer, each added to the residual stream; a final RMS norm and
an untied head. It imports nothing of the program under test and takes
nothing it made.

Latent attention: queries ``h W_q`` split into a part with no position
and a rotary part; the latent ``c = RMSNorm(h W_dkv)``; keys
``[c W_uk, rope(h W_kr)]`` with the rotary key shared by every head;
values ``c W_uv``; a causal softmax at scale ``(qk_nope + qk_rope) ** -0.5
* m ** 2`` where YaRN scaling gives ``m = 0.1 * mscale_all_dim * ln(factor)
+ 1``. The rotary part turns interleaved pairs of dimensions, at YaRN's
interpolated frequencies with cos and sin times ``mscale(factor, mscale) /
mscale(factor, mscale_all_dim)``.

Expert layer: router logits ``h W_router`` in the reference's precision,
softmax scores, the top ``num_experts_per_tok`` experts of each token
with their scores as gates (renormalised to sum to one only where
``norm_topk_prob``, else times ``routed_scaling_factor``); each expert's
SwiGLU applied to the tokens routed to it, weighted by their gates and
added; the shared experts' SwiGLU (one of ``n_shared_experts`` times the
expert width) applied to every token.

Weights. The program draws its weights from the seed by a fixed recipe,
and the reference draws the same ones from the same seed by its own copy
of that recipe (``_keys``, ``layer_weights``): float32 normal draws, each
scaled by the inverse square root of its fan-in (the embedding and the
head by 0.02), norm scales of one. A program may store them in any type;
it has to round from these draws.

Precision. ``"float32"`` computes every product in float32 at the highest
matmul precision, routing included; it is the reference. ``"float8"``
rounds both operands of every product (weights, activations, router
logits' operands, attention scores and probabilities) to float8 e4m3 and
accumulates in float32: the control, one precision below the bfloat16
that the configuration states. Each precision routes by its own scores.

Memory. The layers are drawn one at a time and applied to every sequence
before the next is drawn; attention runs over blocks of rows small enough
that a block's scores fit in about two gigabytes; the experts take the
tokens routed to them in one padded batch per layer.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: Bytes of attention scores that one block of rows may hold.
SCORE_BYTES = 2 << 30


class Yarn(NamedTuple):
    factor: float
    original: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float


class Dims(NamedTuple):
    d: int
    heads: int
    nope: int
    rope: int
    v: int
    latent: int
    ff_dense: int
    ff: int
    experts: int
    top_k: int
    shared: int
    dense_layers: int
    vocab: int
    layers: int
    theta: float
    eps: float
    norm_topk: bool
    routed_scale: float
    yarn: Optional[Yarn]


def dims(cfg: Dict) -> Dims:
    rs = cfg.get("rope_scaling")
    yarn = None
    if rs is not None:
        if rs.get("type") != "yarn":
            raise ValueError(f"rope_scaling {rs.get('type')!r}: the "
                             f"reference has YaRN only")
        yarn = Yarn(float(rs["factor"]),
                    int(rs["original_max_position_embeddings"]),
                    float(rs.get("beta_fast", 32)),
                    float(rs.get("beta_slow", 1)),
                    float(rs.get("mscale", 1)),
                    float(rs.get("mscale_all_dim", 0)))
    return Dims(cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                cfg["v_head_dim"], cfg["kv_lora_rank"],
                cfg["intermediate_size"], cfg["moe_intermediate_size"],
                cfg["n_routed_experts"], cfg["num_experts_per_tok"],
                cfg["n_shared_experts"], cfg["first_k_dense_replace"],
                cfg["vocab_size"], cfg["num_hidden_layers"],
                float(cfg["rope_theta"]), float(cfg["rms_norm_eps"]),
                bool(cfg.get("norm_topk_prob", False)),
                float(cfg.get("routed_scaling_factor", 1.0)), yarn)


# ------------------------------------------------------------ the weights
def _keys(dm: Dims, seed: int):
    """The draw's top keys: embedding, head, two spare, then one a stack
    of like layers (the dense ones, if any, then the expert ones)."""
    stacks = 2 if dm.dense_layers else 1
    return jax.random.split(jax.random.PRNGKey(seed), stacks + 4)


@functools.partial(jax.jit, static_argnums=0)
def embedding(dm: Dims, seed: int):
    return jax.random.normal(_keys(dm, seed)[0], (dm.vocab, dm.d),
                             jnp.float32) * 0.02


@functools.partial(jax.jit, static_argnums=0)
def head(dm: Dims, seed: int):
    return jax.random.normal(_keys(dm, seed)[1], (dm.d, dm.vocab)) \
        * 0.02


def _normal(k, shape, scale):
    return jax.random.normal(k, shape, jnp.float32) * scale


def _swiglu_weights(key, d: int, ff: int) -> Dict:
    k = jax.random.split(key, 3)
    return {"gate": _normal(k[0], (d, ff), 1.0 / math.sqrt(d)),
            "up": _normal(k[1], (d, ff), 1.0 / math.sqrt(d)),
            "down": _normal(k[2], (ff, d), 1.0 / math.sqrt(ff))}


@functools.partial(jax.jit, static_argnums=(0, 2))
def layer_weights(dm: Dims, seed: int, layer: int) -> Dict:
    dense = layer < dm.dense_layers
    stack, index, count = ((0, layer, dm.dense_layers) if dense else
                           (1 if dm.dense_layers else 0,
                            layer - dm.dense_layers,
                            dm.layers - dm.dense_layers))
    key = jax.random.split(
        jax.random.fold_in(_keys(dm, seed)[3 + stack], 0),
        count)[index]
    ks = jax.random.split(key, 6)
    ka = jax.random.split(ks[0], 8)
    s = 1.0 / math.sqrt(dm.d)
    w = {
        "wq": _normal(ka[1], (dm.d, dm.heads, dm.nope + dm.rope), s),
        "wdkv": _normal(ka[2], (dm.d, dm.latent), s),
        "wkr": _normal(ka[3], (dm.d, dm.rope), s),
        "wuk": jax.random.normal(ka[4], (dm.latent, dm.heads, dm.nope))
        / math.sqrt(dm.latent),
        "wuv": jax.random.normal(ka[5], (dm.latent, dm.heads, dm.v))
        / math.sqrt(dm.latent),
        "wo": jax.random.normal(ka[6], (dm.heads, dm.v, dm.d))
        / math.sqrt(dm.heads * dm.v),
    }
    if dense:
        w["mlp"] = _swiglu_weights(ks[2], dm.d, dm.ff_dense)
        return w
    km = jax.random.split(ks[2], 5)
    w["router"] = _normal(km[0], (dm.d, dm.experts), s)
    w["gate"] = _normal(km[1], (dm.experts, dm.d, dm.ff), s)
    w["up"] = _normal(km[2], (dm.experts, dm.d, dm.ff), s)
    w["down"] = _normal(km[3], (dm.experts, dm.ff, dm.d),
                        1.0 / math.sqrt(dm.ff))
    w["shared"] = _swiglu_weights(km[4], dm.d, dm.shared * dm.ff)
    return w


def prompt(seed: int, batch: int, prompt_len: int, vocab: int) -> jnp.ndarray:
    """A job's prompt, drawn from its own seed."""
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, prompt_len),
                              0, vocab)


# --------------------------------------------------------- rotary, YaRN
def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary_frequencies(dm: Dims) -> np.ndarray:
    """Inverse frequencies of the rotary part: ``theta ** (-2i / r)``, and
    under YaRN those below the correction range divided by ``factor``, a
    linear ramp between (``yarn_find_correction_range`` and
    ``yarn_linear_ramp_mask`` of ``modeling_deepseek``)."""
    r = dm.rope
    base = 1.0 / dm.theta ** (np.arange(0, r, 2, dtype=np.float32) / r)
    y = dm.yarn
    if y is None:
        return base

    def dim(rotations):
        return (r * math.log(y.original / (rotations * 2 * math.pi))
                / (2 * math.log(dm.theta)))

    low = max(math.floor(dim(y.beta_fast)), 0)
    high = min(math.ceil(dim(y.beta_slow)), r - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(r // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    return (base / y.factor * ramp + base * (1.0 - ramp)).astype(np.float32)


def softmax_scale(dm: Dims) -> float:
    scale = (dm.nope + dm.rope) ** -0.5
    if dm.yarn is not None and dm.yarn.mscale_all_dim:
        m = yarn_mscale(dm.yarn.factor, dm.yarn.mscale_all_dim)
        scale *= m * m
    return scale


def _cos_sin_scale(dm: Dims) -> float:
    y = dm.yarn
    if y is None:
        return 1.0
    return yarn_mscale(y.factor, y.mscale) / yarn_mscale(y.factor,
                                                         y.mscale_all_dim)


def _rope(dm: Dims, x):
    """x: [r, S, heads, rope]. Pair ``(x[2i], x[2i+1])`` is turned by
    ``position * inv_freq[i]``; the result lists the pairs' first members,
    then their second, as ``apply_rotary_pos_emb`` of ``modeling_deepseek``
    leaves them."""
    ang = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
           * rotary_frequencies(dm))
    c = _cos_sin_scale(dm)
    cos, sin = (jnp.cos(ang) * c)[:, None, :], (jnp.sin(ang) * c)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


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


def _swiglu(w: Dict, h, precision: str, spec=("...d,df->...f",
                                              "...f,fd->...d")):
    a = jax.nn.silu(_ein(spec[0], h, w["gate"], precision))
    a = a * _ein(spec[0], h, w["up"], precision)
    return _ein(spec[1], a, w["down"], precision)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _attention(dm: Dims, w: Dict, x, precision: str):
    """x plus one layer's latent attention over x: [r, S, d] float32."""
    S = x.shape[1]
    h = _rms(x, dm.eps)
    q = _ein("rsd,dhk->rshk", h, w["wq"], precision)
    q_nope, q_rope = q[..., :dm.nope], _rope(dm, q[..., dm.nope:])
    c = _rms(_ein("rsd,dl->rsl", h, w["wdkv"], precision), dm.eps)
    k_rope = _rope(dm, _ein("rsd,dk->rsk", h, w["wkr"], precision)
                   [:, :, None, :])[:, :, 0]
    k_nope = _ein("rsl,lhk->rshk", c, w["wuk"], precision)
    v = _ein("rsl,lhk->rshk", c, w["wuv"], precision)
    scores = (_ein("rqhk,rshk->rhqs", q_nope, k_nope, precision)
              + _ein("rqhk,rsk->rhqs", q_rope, k_rope, precision))
    scores = scores * softmax_scale(dm)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = _ein("rhqs,rshk->rqhk", probs, v, precision)
    return x + _ein("rshk,hkd->rsd", o, w["wo"], precision)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _dense_ffn(dm: Dims, w: Dict, x, precision: str):
    return x + _swiglu(w, _rms(x, dm.eps), precision)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _route(dm: Dims, router, x, precision: str):
    """The normed input ``[N, d]`` of the expert layer over x ``[r, S, d]``,
    and each token's experts and gates ``[N, top_k]``."""
    h = _rms(x, dm.eps).reshape(-1, dm.d)
    scores = jax.nn.softmax(_ein("nd,de->ne", h, router, precision), -1)
    gates, experts = jax.lax.top_k(scores, dm.top_k)
    if dm.norm_topk:
        gates = gates / gates.sum(-1, keepdims=True)
    else:
        gates = gates * dm.routed_scale
    return h, experts, gates


def assignments(experts: np.ndarray, gates: np.ndarray, n_experts: int
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Each expert's tokens and their gates, ``[E, C]`` each, where ``C``
    is the busiest expert's count rounded up to a power of two (so that
    few shapes compile); slots past an expert's count hold token 0 at
    gate 0."""
    top_k = experts.shape[1]
    flat = experts.reshape(-1)
    counts = np.bincount(flat, minlength=n_experts)
    cap = 1 << max(int(counts.max()) - 1, 0).bit_length()
    order = np.argsort(flat, kind="stable")
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    slot = np.arange(flat.size) - starts[flat[order]]
    tokens = np.zeros((n_experts, cap), np.int32)
    weights = np.zeros((n_experts, cap), np.float32)
    tokens[flat[order], slot] = order // top_k
    weights[flat[order], slot] = gates.reshape(-1)[order]
    return tokens, weights


@functools.partial(jax.jit, static_argnums=(0, 5))
def _experts(dm: Dims, w: Dict, h, tokens, weights, precision: str):
    """The routed experts' weighted sum ``[N, d]``: expert ``e`` applied to
    the rows ``tokens[e]`` of h, times ``weights[e]``."""
    y = _swiglu(w, h[tokens], precision, ("ecd,edf->ecf", "ecf,efd->ecd"))
    y = y * weights[..., None]
    return jnp.zeros_like(h).at[tokens.reshape(-1)].add(
        y.reshape(-1, dm.d))


@functools.partial(jax.jit, static_argnums=(0, 3))
def _shared(dm: Dims, w: Dict, h, precision: str):
    return _swiglu(w, h, precision)


def _moe(dm: Dims, w: Dict, x, precision: str):
    """x plus the expert layer over x ``[r, S, d]``."""
    h, experts, gates = _route(dm, w["router"], x, precision)
    tokens, weights = assignments(np.asarray(experts), np.asarray(gates),
                                  dm.experts)
    routed = _experts(dm, {k: w[k] for k in ("gate", "up", "down")}, h,
                      jnp.asarray(tokens), jnp.asarray(weights), precision)
    out = routed + _shared(dm, w["shared"], h, precision)
    return x + out.reshape(x.shape)


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


def forward(cfg: Dict, weight_seed: int, tokens: jnp.ndarray, *,
            start: int, precisions: Tuple[str, ...] = ("float32",)
            ) -> Dict[str, jnp.ndarray]:
    """Logits ``[rows, S - start, vocab]`` of the full forward over
    ``tokens`` ``[rows, S]``, at the positions from ``start`` on, for each
    precision."""
    dm = dims(cfg)
    with jax.default_matmul_precision("highest"):
        table = embedding(dm, weight_seed)
        xs = {p: _embed(dm, table, tokens, p) for p in precisions}
        del table
        rows, S = tokens.shape
        block = _row_block(dm, rows, S)
        pad = -(-rows // block) * block - rows
        xs = {p: jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
              for p, x in xs.items()}
        for layer in range(dm.layers):
            w = layer_weights(dm, weight_seed, layer)
            for p, x in xs.items():
                x = jnp.concatenate(
                    [_attention(dm, w, x[r:r + block], p)
                     for r in range(0, x.shape[0], block)], axis=0)
                xs[p] = (_dense_ffn(dm, w["mlp"], x, p) if "mlp" in w
                         else _moe(dm, w, x, p))
            del w
        w_head = head(dm, weight_seed)
        return {p: _logits(dm, w_head, x[:rows], start, p)
                for p, x in xs.items()}


def served_logits(cfg: Dict, weight_seed: int, jobs: List[Sequences], *,
                  seq_len: int, rows: int,
                  precisions: Tuple[str, ...] = ("float32",),
                  ) -> Dict[str, jnp.ndarray]:
    """Logits ``[rows, seq_len - prompt_len + 1, vocab]`` at the positions
    where the jobs' served tokens were chosen, for each precision. Each
    row is a sequence of a job: its prompt followed by its served tokens
    but the last, as the program fed them. Every call has the same shape:
    sequences are padded at the end to ``seq_len`` (causal attention keeps
    the padding out of the positions before it, and routing drops no
    token, so padded tokens take no expert's room) and the rows to
    ``rows`` with sequences of token 0; what lies past a job's tokens is
    to be left out by the caller."""
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
    return forward(cfg, weight_seed, tokens, start=prompt_len - 1,
                   precisions=precisions)
