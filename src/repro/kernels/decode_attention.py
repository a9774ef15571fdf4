"""Pallas TPU decode attention: one query token per sequence against a
padded KV cache (flash-decode).

Grid (batch, kv_block) with kv_block innermost: the online-softmax state
for the single query position lives in VMEM scratch; each step streams one
[block_k, D] cache tile from HBM into VMEM — decode is bandwidth-bound, so
the tile size trades VMEM footprint against DMA efficiency.  The ``[B]``
lengths are scalar-prefetched into SMEM: a rank-1 VMEM block of one
element is not a legal TPU tile.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            block_k: int, n_k: int, n_kv: int, scale: float):
    b = pl.program_id(0)
    kj = pl.program_id(1)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    # One 2-D [G, D] x [D, bk] product per KV head: the MXU takes plain
    # matmuls, and a batched einsum over the cache's head axis would need a
    # transposed copy of the whole tile in VMEM.
    for h in range(n_kv):
        k = k_ref[0, :, h, :]                           # [bk, D]
        v = v_ref[0, :, h, :]                           # [bk, Dv]
        q = (q_ref[0, h].astype(jnp.float32) * scale).astype(k.dtype)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        valid = k_pos < length
        s = jnp.where(valid, s, NEG_INF)                # [G, bk]

        m_prev = m_scr[h]                               # [G, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_scr[h] = l_scr[h] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [G, Dv]
        m_scr[h] = m_new

    @pl.when(kj == n_k - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def decode_attention_pallas(
    q: jnp.ndarray,          # [B, H, D]
    k_cache: jnp.ndarray,    # [B, S, KV, D]
    v_cache: jnp.ndarray,    # [B, S, KV, Dv]
    length: jnp.ndarray,     # [B]
    *,
    scale: Optional[float] = None,
    block_k: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    B, H, D = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    Dv = v_cache.shape[-1]
    G = H // KV
    scale = scale if scale is not None else D ** -0.5
    block_k = min(block_k, S)
    pad = (-S) % block_k
    kc, vc = k_cache, v_cache
    if pad:
        kc = jnp.pad(kc, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(vc, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_k = kc.shape[1] // block_k
    qr = q.reshape(B, KV, G, D)

    kernel = functools.partial(_kernel, block_k=block_k, n_k=n_k, n_kv=KV,
                               scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n_k),
        in_specs=[
            pl.BlockSpec((1, KV, G, D), lambda b, j, lens: (b, 0, 0, 0)),
            pl.BlockSpec((1, block_k, KV, D), lambda b, j, lens: (b, j, 0, 0)),
            pl.BlockSpec((1, block_k, KV, Dv),
                         lambda b, j, lens: (b, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, G, Dv),
                               lambda b, j, lens: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, Dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, Dv), q.dtype),
        interpret=interpret,
    )(length.astype(jnp.int32), qr, kc, vc)
    return out.reshape(B, H, Dv)
