"""Shared model building blocks (functional JAX, params as pytrees).

Conventions:

* Parameters are stored in fp32 and cast to ``compute_dtype`` (bf16 by
  default) at use; optimizer state stays fp32.  The served weight set
  (``core.jobs.serve_weights``) holds the matrix weights in the compute
  dtype already, so there the cast is a no-op.
* Layer-stacked parameters carry a leading ``[n_layers, ...]`` axis and are
  consumed by ``jax.lax.scan`` so the lowered HLO stays compact for the
  512-device dry-run.
* Weight shapes put the contraction (input) dim first: ``w[d_in, d_out]``.
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_COMPUTE_DTYPE = jnp.bfloat16


def cast(x: jnp.ndarray, dtype) -> jnp.ndarray:
    return x.astype(dtype) if x.dtype != dtype else x


# ------------------------------------------------------------------ linear
def linear_init(key, d_in: int, d_out: int, scale: Optional[float] = None,
                bias: bool = False) -> Dict:
    if scale is None:
        scale = 1.0 / math.sqrt(d_in)
    p = {"w": jax.random.normal(key, (d_in, d_out), jnp.float32) * scale}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def linear(p: Dict, x: jnp.ndarray, dtype=DEFAULT_COMPUTE_DTYPE) -> jnp.ndarray:
    y = x @ cast(p["w"], dtype)
    if "b" in p:
        y = y + cast(p["b"], dtype)
    return y


# ------------------------------------------------------------------- norms
def norm_init(d: int, kind: str = "rms") -> Dict:
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if kind == "layer":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def apply_norm(p: Dict, x: jnp.ndarray, kind: str = "rms",
               eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if kind == "rms":
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        xf = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = xf * p["scale"]
    if "bias" in p:
        out = out + p["bias"]
    return out.astype(x.dtype)


# -------------------------------------------------------------- embeddings
def embedding_init(key, vocab: int, d: int) -> Dict:
    return {"table": jax.random.normal(key, (vocab, d), jnp.float32) * 0.02}


def embed(p: Dict, tokens: jnp.ndarray,
          dtype=DEFAULT_COMPUTE_DTYPE) -> jnp.ndarray:
    return cast(p["table"], dtype)[tokens]


def unembed(p: Dict, x: jnp.ndarray,
            dtype=DEFAULT_COMPUTE_DTYPE) -> jnp.ndarray:
    return x @ cast(p["table"], dtype).T


# -------------------------------------------------------------------- rope
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """Rotary embedding.  x: [..., S, H, D]; positions: [..., S]."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                        # [D/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, D/2]
    cos = jnp.cos(angles)[..., :, None, :]              # [..., S, 1, D/2]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term, ``0.1 * mscale * ln(factor) + 1``
    (1 where nothing is interpolated)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_rope_freqs(head_dim: int, theta: float, scaling) -> np.ndarray:
    """Inverse frequencies ``[head_dim / 2]`` of YaRN rotary scaling
    (``configs.base.RopeScaling``), as DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding`` computes them: dimensions that turn
    more than ``beta_fast`` times over the original context keep their
    frequency, those that turn fewer than ``beta_slow`` times are divided
    by ``factor``, and a linear ramp blends the two between."""
    def correction_dim(rotations):
        return (head_dim * math.log(scaling.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(correction_dim(scaling.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                            / head_dim)
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    return (extra / scaling.factor * ramp + extra * (1.0 - ramp)
            ).astype(np.float32)


def apply_rope_pairs(x: jnp.ndarray, positions: jnp.ndarray,
                     inv_freq: np.ndarray, scale: float = 1.0) -> jnp.ndarray:
    """Rotary embedding over interleaved pairs ``(x[2i], x[2i+1])`` turned
    by ``positions * inv_freq[i]``, with cos and sin times ``scale``; the
    result holds the pairs' first members, then their second (the layout
    of DeepSeek-V2's ``apply_rotary_pos_emb``).  x: [..., S, H, D]."""
    angles = positions[..., :, None].astype(jnp.float32) * inv_freq
    cos = (jnp.cos(angles) * scale)[..., :, None, :]
    sin = (jnp.sin(angles) * scale)[..., :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------- mlp
def mlp_init(key, d_model: int, d_ff: int, act: str) -> Dict:
    ks = jax.random.split(key, 3)
    if act in ("swiglu", "geglu"):
        return {
            "gate": linear_init(ks[0], d_model, d_ff),
            "up": linear_init(ks[1], d_model, d_ff),
            "down": linear_init(ks[2], d_ff, d_model),
        }
    return {
        "up": linear_init(ks[0], d_model, d_ff, bias=True),
        "down": linear_init(ks[1], d_ff, d_model, bias=True),
    }


def apply_mlp(p: Dict, x: jnp.ndarray, act: str,
              dtype=DEFAULT_COMPUTE_DTYPE, whook=None) -> jnp.ndarray:
    """``whook`` optionally post-processes each cast weight (e.g. a sharding
    constraint forcing weight-side gathers under full-mesh batch plans)."""
    def lin(q, v):
        w = cast(q["w"], dtype)
        if whook is not None:
            w = whook(w)
        y = v @ w
        if "b" in q:
            y = y + cast(q["b"], dtype)
        return y

    if act == "swiglu":
        h = jax.nn.silu(lin(p["gate"], x)) * lin(p["up"], x)
    elif act == "geglu":
        h = jax.nn.gelu(lin(p["gate"], x)) * lin(p["up"], x)
    else:
        h = jax.nn.gelu(lin(p["up"], x))
    return lin(p["down"], h)


# ---------------------------------------------------------------- utility
def stack_layers(init_fn, key, n_layers: int) -> Dict:
    """Initialize ``n_layers`` identical layers stacked on a leading axis."""
    keys = jax.random.split(key, n_layers)
    return jax.vmap(init_fn)(keys)


def causal_mask(s_q: int, s_k: int, q_offset) -> jnp.ndarray:
    """[s_q, s_k] True where query may attend (supports KV-cache offsets)."""
    q_pos = q_offset + jnp.arange(s_q)[:, None]
    k_pos = jnp.arange(s_k)[None, :]
    return k_pos <= q_pos


def window_mask(s_q: int, s_k: int, q_offset, window: int) -> jnp.ndarray:
    q_pos = q_offset + jnp.arange(s_q)[:, None]
    k_pos = jnp.arange(s_k)[None, :]
    return (k_pos <= q_pos) & (k_pos > q_pos - window)
