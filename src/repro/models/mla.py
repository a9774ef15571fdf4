"""Multi-head Latent Attention (DeepSeek-V2 / MiniCPM3).

Training/prefill use the naive expansion (decompress the latent KV per
position, then standard attention).  Decode uses the absorbed formulation:
queries are projected into the latent space so the cache stays compressed
(``kv_lora_rank + qk_rope_dim`` per token instead of
``n_heads * (qk_nope + v_dim)``) — this is MLA's serving advantage and
dramatically raises decode "residency" in the scheduler's sense.

The rotary part follows DeepSeek-V2's ``modeling_deepseek``: its dimensions
turn in interleaved pairs, and under YaRN scaling (``rope_scaling``, the
architecture's published ``configs.base.RopeScaling``) the frequencies are
interpolated and the softmax scale gains the factor
``yarn_mscale(factor, mscale_all_dim) ** 2``, in prefill and decode alike.
Both paths run under ``jax.named_scope("mla")``, so that a profile can
time latent attention apart from the rest of a layer.
"""

from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MLAConfig, RopeScaling
from repro.kernels import ops

from .layers import (
    DEFAULT_COMPUTE_DTYPE,
    apply_norm,
    apply_rope_pairs,
    cast,
    norm_init,
    rope_freqs,
    yarn_mscale,
    yarn_rope_freqs,
)


def mla_init(key, d_model: int, n_heads: int, m: MLAConfig) -> Dict:
    ks = jax.random.split(key, 8)
    s = 1.0 / math.sqrt(d_model)
    qk_dim = m.qk_nope_dim + m.qk_rope_dim
    p: Dict = {}
    if m.q_lora_rank:
        p["wdq"] = jax.random.normal(ks[0], (d_model, m.q_lora_rank)) * s
        p["q_norm"] = norm_init(m.q_lora_rank)
        p["wuq"] = jax.random.normal(
            ks[1], (m.q_lora_rank, n_heads, qk_dim)) / math.sqrt(m.q_lora_rank)
    else:
        p["wq"] = jax.random.normal(ks[1], (d_model, n_heads, qk_dim)) * s
    p["wdkv"] = jax.random.normal(ks[2], (d_model, m.kv_lora_rank)) * s
    p["kv_norm"] = norm_init(m.kv_lora_rank)
    p["wkr"] = jax.random.normal(ks[3], (d_model, m.qk_rope_dim)) * s
    p["wuk"] = jax.random.normal(
        ks[4], (m.kv_lora_rank, n_heads, m.qk_nope_dim)) / math.sqrt(m.kv_lora_rank)
    p["wuv"] = jax.random.normal(
        ks[5], (m.kv_lora_rank, n_heads, m.v_head_dim)) / math.sqrt(m.kv_lora_rank)
    p["wo"] = jax.random.normal(
        ks[6], (n_heads, m.v_head_dim, d_model)) / math.sqrt(n_heads * m.v_head_dim)
    return p


class _Rotary(NamedTuple):
    inv_freq: np.ndarray | jnp.ndarray   # [qk_rope_dim / 2]
    cos_sin_scale: float
    softmax_scale: float


def _rotary(m: MLAConfig, rope_theta: float,
            s: Optional[RopeScaling]) -> _Rotary:
    """The rotary part's frequencies and factors, and the softmax scale."""
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    if s is None:
        return _Rotary(rope_freqs(m.qk_rope_dim, rope_theta), 1.0, scale)
    all_dim = yarn_mscale(s.factor, s.mscale_all_dim)
    return _Rotary(yarn_rope_freqs(m.qk_rope_dim, rope_theta, s),
                   yarn_mscale(s.factor, s.mscale) / all_dim,
                   scale * all_dim * all_dim)


def _rope(x, positions, rot: _Rotary):
    return apply_rope_pairs(x, positions, rot.inv_freq, rot.cos_sin_scale)


def _queries(p: Dict, x, m: MLAConfig, rot: _Rotary, positions, dtype):
    if "wdq" in p:
        cq = apply_norm(p["q_norm"], x @ cast(p["wdq"], dtype))
        q = jnp.einsum("bsr,rhk->bshk", cq, cast(p["wuq"], dtype))
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, cast(p["wq"], dtype))
    q_nope = q[..., : m.qk_nope_dim]
    q_rope = _rope(q[..., m.qk_nope_dim:], positions, rot)
    return q_nope, q_rope


@jax.named_scope("mla")
def mla_apply(
    p: Dict,
    x: jnp.ndarray,                        # [B, S, D]
    m: MLAConfig,
    *,
    rope_theta: float,
    rope_scaling: Optional[RopeScaling] = None,
    positions: Optional[jnp.ndarray] = None,
    backend: str = "xla",
    shard=None,
    dtype=DEFAULT_COMPUTE_DTYPE,
) -> Tuple[jnp.ndarray, Dict]:
    """Full-sequence MLA (naive expansion).  Returns (out, cache)."""
    B, S, _ = x.shape
    rot = _rotary(m, rope_theta, rope_scaling)
    pos = positions if positions is not None else jnp.arange(S)
    q_nope, q_rope = _queries(p, x, m, rot, pos, dtype)

    c_kv = apply_norm(p["kv_norm"], x @ cast(p["wdkv"], dtype))     # [B,S,R]
    k_rope = _rope((x @ cast(p["wkr"], dtype))[:, :, None, :],
                   pos, rot)                                        # [B,S,1,r]
    if shard is not None:
        c_kv = shard.replicate_seq(c_kv)
        k_rope = shard.replicate_seq(k_rope)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, cast(p["wuk"], dtype))
    v = jnp.einsum("bsr,rhk->bshk", c_kv, cast(p["wuv"], dtype))

    H = q_nope.shape[2]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, k_rope.shape[:2] + (H, m.qk_rope_dim))],
        axis=-1)
    o = ops.flash_attention(q, k, v, mask_kind="causal",
                            scale=rot.softmax_scale, backend=backend)
    y = jnp.einsum("bshk,hkd->bsd", o, cast(p["wo"], dtype))
    return y, {"c_kv": c_kv, "k_rope": k_rope[:, :, 0, :]}


@jax.named_scope("mla")
def mla_decode(
    p: Dict,
    x: jnp.ndarray,                        # [B, D]
    cache: Dict,                           # {"c_kv": [B,S,R], "k_rope": [B,S,r]}
    length: jnp.ndarray,                   # [B]
    m: MLAConfig,
    *,
    rope_theta: float,
    rope_scaling: Optional[RopeScaling] = None,
    dtype=DEFAULT_COMPUTE_DTYPE,
) -> Tuple[jnp.ndarray, Dict]:
    """Absorbed-matmul MLA decode on the compressed cache."""
    B, D = x.shape
    rot = _rotary(m, rope_theta, rope_scaling)
    pos = length[:, None]
    q_nope, q_rope = _queries(p, x[:, None, :], m, rot, pos, dtype)
    q_nope, q_rope = q_nope[:, 0], q_rope[:, 0]          # [B,H,*]

    c_t = apply_norm(p["kv_norm"], x @ cast(p["wdkv"], dtype))       # [B,R]
    kr_t = _rope((x @ cast(p["wkr"], dtype))[:, None, None, :],
                 pos, rot)[:, 0, 0]                                   # [B,r]
    bidx = jnp.arange(B)
    c_cache = cache["c_kv"].at[bidx, length].set(c_t.astype(cache["c_kv"].dtype))
    r_cache = cache["k_rope"].at[bidx, length].set(kr_t.astype(cache["k_rope"].dtype))

    # absorb W_uk into the query: q_lat [B,H,R]
    q_lat = jnp.einsum("bhk,rhk->bhr", q_nope, cast(p["wuk"], dtype))
    logits = (jnp.einsum("bhr,bsr->bhs", q_lat, c_cache) +
              jnp.einsum("bhk,bsk->bhs", q_rope, r_cache)).astype(jnp.float32)
    logits = logits * rot.softmax_scale
    S = c_cache.shape[1]
    valid = jnp.arange(S)[None] < (length + 1)[:, None]
    logits = jnp.where(valid[:, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(dtype)
    ctx = jnp.einsum("bhs,bsr->bhr", probs, c_cache)     # [B,H,R]
    o = jnp.einsum("bhr,rhk->bhk", ctx, cast(p["wuv"], dtype))
    y = jnp.einsum("bhk,hkd->bd", o, cast(p["wo"], dtype))
    return y, {"c_kv": c_cache, "k_rope": r_cache}
