"""Pallas TPU kernel for the Mamba-2 SSD (state-space duality) mixer.

Grid (batch, head, chunk) with the chunk axis innermost: the [P, N] SSD
state for each (batch, head) persists in VMEM scratch across chunk steps
(the sequential inter-chunk recurrence), while each chunk's quadratic
intra-chunk term runs on the MXU from VMEM-resident [Q, P] / [Q, N] tiles.
This is the TPU-native re-blocking of the paper's GPU algorithm: instead of
a warp-level scan, the sequential dimension rides the (ordered) TPU grid.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, state_out_ref,
            state_scr, *, chunk: int, n_chunks: int):
    h = pl.program_id(1)
    cj = pl.program_id(2)

    @pl.when(cj == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0, 0].astype(jnp.float32)              # [Q, P]
    dt = dt_ref[0, 0, 0].astype(jnp.float32)            # [1, Q]
    a = a_ref[h]                                        # scalar (SMEM)
    bm = b_ref[0, 0, 0].astype(jnp.float32)             # [Q, N]
    cm = c_ref[0, 0, 0].astype(jnp.float32)             # [Q, N]

    # Mosaic has no cumsum: take the within-chunk prefix sum as a product
    # with an upper-triangular ones matrix, at full f32 precision, and get
    # the column view of it from a 2-D transpose.
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    da = dt * a                                         # [1, Q], <= 0
    cum = jnp.dot(da, (row <= col).astype(jnp.float32),
                  precision=jax.lax.Precision.HIGHEST,
                  preferred_element_type=jnp.float32)   # [1, Q]
    cum_s = jnp.broadcast_to(cum, (chunk, chunk))       # [t, s] -> cum_s
    cum_t = cum_s.T                                     # [t, s] -> cum_t
    # the chunk's total decay, as rows of equal entries: Mosaic cannot
    # broadcast a [1, 1] value over both sublanes and lanes
    total = cum_t[chunk - 1:, :]                        # [1, Q]
    total_n = jnp.dot(da, jnp.ones((chunk, bm.shape[1]), jnp.float32),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)   # [1, N]

    # intra-chunk quadratic term
    decay = jnp.exp(jnp.where(row >= col, cum_t - cum_s, -jnp.inf))
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())))  # [Q,Q]
    L = scores * decay * dt
    y = jax.lax.dot_general(L, x, (((1,), (0,)), ((), ())))          # [Q,P]

    # inter-chunk contribution from the carried state
    state = state_scr[...]                              # [P, N]
    y = y + jnp.exp(cum_t[:, :1]) * jax.lax.dot_general(
        cm, state, (((1,), (1,)), ((), ())))            # [Q,P]
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    # state update: decay + sum_s exp(total - cum_s) dt_s x_s (x) B_s
    w = jnp.exp(total - cum) * dt                       # [1, Q]
    chunk_state = jax.lax.dot_general(
        x.T * w, bm, (((1,), (0,)), ((), ())))          # [P, N]
    state_scr[...] = state * jnp.exp(total_n) + chunk_state

    @pl.when(cj == n_chunks - 1)
    def _finish():
        state_out_ref[0, 0] = state_scr[...]


def ssd_pallas(
    x: jnp.ndarray,          # [B, S, H, P]
    dt: jnp.ndarray,         # [B, S, H]
    A: jnp.ndarray,          # [H]
    Bmat: jnp.ndarray,       # [B, S, G, N]
    Cmat: jnp.ndarray,       # [B, S, G, N]
    *,
    chunk: int = 128,
    initial_state: Optional[jnp.ndarray] = None,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if initial_state is not None:
        raise ValueError("ssd_pallas starts from a zero state; resumed "
                         "scans go through ops.ssd")
    B, S, H, P = x.shape
    G, N = Bmat.shape[2], Bmat.shape[3]
    rep = H // G
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q

    xr = x.transpose(0, 2, 1, 3).reshape(B, H, nc, Q, P)
    dtr = dt.transpose(0, 2, 1).reshape(B, H, nc, 1, Q)   # [1, Q] tiles
    br = Bmat.transpose(0, 2, 1, 3).reshape(B, G, nc, Q, N)
    cr = Cmat.transpose(0, 2, 1, 3).reshape(B, G, nc, Q, N)

    kernel = functools.partial(_kernel, chunk=Q, n_chunks=nc)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P),
                         lambda b, h, j, a: (b, h, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, Q),
                         lambda b, h, j, a: (b, h, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, N),
                         lambda b, h, j, a, rep=rep: (b, h // rep, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, N),
                         lambda b, h, j, a, rep=rep: (b, h // rep, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, P),
                         lambda b, h, j, a: (b, h, j, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, j, a: (b, h, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
    )
    y, state = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc, Q, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        interpret=interpret,
    )(A.astype(jnp.float32), xr, dtr, br, cr)
    y = y.reshape(B, H, S, P).transpose(0, 2, 1, 3)
    return y, state
