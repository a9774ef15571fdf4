"""Pure-jnp reference oracles for every kernel in this package.

These are the semantic ground truth: simple, quadratic/sequential,
numerically straightforward.  The efficient XLA implementations in
``ops.py`` and the Pallas TPU kernels are tested against these with
``assert_allclose`` over shape/dtype sweeps (see tests/test_kernels.py).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def attention(
    q: jnp.ndarray,          # [B, Sq, H, D]
    k: jnp.ndarray,          # [B, Sk, KV, D]
    v: jnp.ndarray,          # [B, Sk, KV, Dv]
    mask: Optional[jnp.ndarray] = None,   # [Sq, Sk] bool, True = attend
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Exact GQA attention (quadratic).  Returns [B, Sq, H, Dv]."""
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    assert H % KV == 0, (H, KV)
    G = H // KV
    scale = scale if scale is not None else D ** -0.5
    qf = q.astype(jnp.float32).reshape(B, Sq, KV, G, D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf) * scale
    if mask is not None:
        logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)  # fully-masked rows
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, vf)
    return out.reshape(B, Sq, H, -1).astype(q.dtype)


def decode_attention(
    q: jnp.ndarray,          # [B, H, D] single query token
    k_cache: jnp.ndarray,    # [B, S, KV, D]
    v_cache: jnp.ndarray,    # [B, S, KV, Dv]
    length: jnp.ndarray,     # [B] valid cache lengths
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """One-token attention against a (padded) KV cache.  [B, H, Dv]."""
    B, H, D = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = scale if scale is not None else D ** -0.5
    qf = q.astype(jnp.float32).reshape(B, KV, G, D)
    logits = jnp.einsum("bhgd,bshd->bhgs", qf, k_cache.astype(jnp.float32))
    logits = logits * scale
    valid = jnp.arange(S)[None] < length[:, None]          # [B, S]
    logits = jnp.where(valid[:, None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", probs, v_cache.astype(jnp.float32))
    return out.reshape(B, H, -1).astype(q.dtype)


def ssd_scan(
    x: jnp.ndarray,          # [B, S, H, P]
    dt: jnp.ndarray,         # [B, S, H]        (softplus already applied)
    A: jnp.ndarray,          # [H]              (negative)
    Bmat: jnp.ndarray,       # [B, S, G, N]
    Cmat: jnp.ndarray,       # [B, S, G, N]
    initial_state: Optional[jnp.ndarray] = None,  # [B, H, P, N]
) -> tuple:
    """Mamba-2 SSD recurrence, sequential reference.

    h_t = exp(A dt_t) * h_{t-1} + dt_t * x_t B_t^T    (outer product P x N)
    y_t = h_t C_t
    Returns (y [B,S,H,P], final_state [B,H,P,N]).
    """
    Bsz, S, H, P = x.shape
    G, N = Bmat.shape[2], Bmat.shape[3]
    rep = H // G
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    Bf = jnp.repeat(Bmat.astype(jnp.float32), rep, axis=2)   # [B,S,H,N]
    Cf = jnp.repeat(Cmat.astype(jnp.float32), rep, axis=2)
    h0 = (initial_state.astype(jnp.float32) if initial_state is not None
          else jnp.zeros((Bsz, H, P, N), jnp.float32))

    def step(h, inp):
        xt, dtt, bt, ct = inp                                # [B,H,P],[B,H],[B,H,N]x2
        decay = jnp.exp(Af[None] * dtt)                      # [B,H]
        h = h * decay[..., None, None] + \
            (dtt[..., None] * xt)[..., None] * bt[..., None, :]
        y = jnp.einsum("bhpn,bhn->bhp", h, ct)
        return h, y

    xs = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
          jnp.moveaxis(Bf, 1, 0), jnp.moveaxis(Cf, 1, 0))
    hT, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1)                               # [B,S,H,P]
    return y.astype(x.dtype), hT


def rglru_scan(
    x: jnp.ndarray,          # [B, S, C] gated input
    gate_a: jnp.ndarray,     # [B, S, C] recurrence gate pre-activation in (0,1)
    gate_i: jnp.ndarray,     # [B, S, C] input gate in (0,1)
    log_a: jnp.ndarray,      # [C] per-channel base decay (log, negative)
    initial_state: Optional[jnp.ndarray] = None,  # [B, C]
    c: float = 8.0,
) -> tuple:
    """RG-LRU recurrence (RecurrentGemma), sequential reference.

    a_t = exp(c * log_a * r_t);  h_t = a_t h_{t-1} + sqrt(1 - a_t^2) (i_t x_t)
    Returns (h [B,S,C], final_state [B,C]).
    """
    Bsz, S, C = x.shape
    xf = x.astype(jnp.float32)
    rf = gate_a.astype(jnp.float32)
    inf_ = gate_i.astype(jnp.float32)
    la = log_a.astype(jnp.float32)
    h0 = (initial_state.astype(jnp.float32) if initial_state is not None
          else jnp.zeros((Bsz, C), jnp.float32))

    def step(h, inp):
        xt, rt, it = inp
        log_at = c * la[None] * rt                           # [B,C], <= 0
        at = jnp.exp(log_at)
        beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_at), 0.0))
        h = at * h + beta * (it * xt)
        return h, h

    xs = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(rf, 1, 0),
          jnp.moveaxis(inf_, 1, 0))
    hT, hs = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(hs, 0, 1).astype(x.dtype), hT


def moe_dense(
    x: jnp.ndarray,          # [T, D] tokens
    gate_w: jnp.ndarray,     # [E, D, F]
    up_w: jnp.ndarray,       # [E, D, F]
    down_w: jnp.ndarray,     # [E, F, D]
    probs: jnp.ndarray,      # [T, E] routing weights (0 where unrouted)
) -> jnp.ndarray:
    """Dense-einsum MoE oracle: every token through every expert, weighted.

    O(T*E*D*F) — only usable at test sizes; the efficient path sorts the
    assignments by expert into grouped products (ops.moe_apply).
    """
    xf = x.astype(jnp.float32)
    h = jnp.einsum("td,edf->tef", xf, gate_w.astype(jnp.float32))
    u = jnp.einsum("td,edf->tef", xf, up_w.astype(jnp.float32))
    h = jax.nn.silu(h) * u
    y = jnp.einsum("tef,efd->ted", h, down_w.astype(jnp.float32))
    return jnp.einsum("ted,te->td", y, probs.astype(jnp.float32)).astype(x.dtype)
