"""Pallas TPU flash attention (causal / sliding-window / full) with GQA.

Tiling: grid (batch, kv_head, q_block, kv_block); the kv_block axis is the
innermost (sequential) grid dimension, carrying the online-softmax state
(m, l, acc) in VMEM scratch across kv blocks — the canonical TPU flash
pattern.  Block shapes keep the working set in VMEM and the matmul operands
MXU-aligned (block_q x D and block_k x D tiles, D a multiple of 128 for the
zoo's head dims).

Validated against the pure-jnp oracle in interpret mode on CPU
(tests/test_kernels.py, ``interpret=True``) and compiled for v5e at yi-6b
widths (tests/test_chip_compile.py); TPU is the compilation target.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            mask_kind: str, window: int, block_q: int, block_k: int,
            n_k: int, sq: int, sk: int, scale: float, q_offset: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # [G, bq, D]
    k = k_ref[0, 0].astype(jnp.float32)                  # [bk, D]
    v = v_ref[0, 0].astype(jnp.float32)                  # [bk, Dv]

    s = jax.lax.dot_general(q, k, (((2,), (1,)), ((), ())))  # [G, bq, bk]
    q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_q, block_k), 1)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_q, block_k), 2)
    valid = k_pos < sk
    if mask_kind == "causal":
        valid = valid & (k_pos <= q_pos)
    elif mask_kind == "window":
        valid = valid & (k_pos <= q_pos) & (k_pos > q_pos - window)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]                                  # [G, bq]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(valid, p, 0.0)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
    acc_scr[...] = acc_scr[...] * corr[..., None] + jax.lax.dot_general(
        p, v, (((2,), (0,)), ((), ())))                  # [G, bq, Dv]
    m_scr[...] = m_new

    @pl.when(kj == n_k - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom[..., None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray,          # [B, Sq, H, D]
    k: jnp.ndarray,          # [B, Sk, KV, D]
    v: jnp.ndarray,          # [B, Sk, KV, Dv]
    *,
    mask_kind: str = "causal",
    window: int = 0,
    q_offset: int = 0,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // KV
    scale = scale if scale is not None else D ** -0.5
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)

    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_k
    qr = q.reshape(B, Sq, KV, G, D).transpose(0, 2, 3, 1, 4)  # [B,KV,G,Sq,D]
    if pad_q:
        qr = jnp.pad(qr, ((0, 0),) * 3 + ((0, pad_q), (0, 0)))
    kr = k.transpose(0, 2, 1, 3)                              # [B,KV,Sk,D]
    vr = v.transpose(0, 2, 1, 3)
    if pad_k:
        kr = jnp.pad(kr, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vr = jnp.pad(vr, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    n_q = qr.shape[3] // block_q
    n_k = kr.shape[2] // block_k

    kernel = functools.partial(
        _kernel, mask_kind=mask_kind, window=window, block_q=block_q,
        block_k=block_k, n_k=n_k, sq=Sq, sk=Sk, scale=scale,
        q_offset=int(q_offset))

    out = pl.pallas_call(
        kernel,
        grid=(B, KV, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, G, block_q, D),
                         lambda b, h, i, j: (b, h, 0, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, Dv),
                         lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, block_q, Dv),
                               lambda b, h, i, j: (b, h, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, qr.shape[3], Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, block_q), jnp.float32),
            pltpu.VMEM((G, block_q), jnp.float32),
            pltpu.VMEM((G, block_q, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, qr.shape[3], H, Dv)
    return out[:, :Sq]
