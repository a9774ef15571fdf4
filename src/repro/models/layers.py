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
