"""Pallas TPU kernel for the RG-LRU gated linear recurrence.

Grid (batch, chunk) with the chunk axis innermost and the [C] hidden state
in VMEM scratch.  Within a chunk the recurrence is evaluated by a
``fori_loop`` over time steps — each step is a pure VPU (elementwise)
update of one ``[1, C]`` row across the channel lanes, so the kernel is
bandwidth-bound exactly like the recurrence itself; chunking exists to
bound the VMEM-resident gate/input tiles.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, ga_ref, gi_ref, loga_ref, h_ref, state_out_ref,
            state_scr, *, chunk: int, n_chunks: int, c_const: float,
            rows_per_group: int):
    cj = pl.program_id(1)

    @pl.when(cj == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    la = loga_ref[...].astype(jnp.float32)              # [1, C]

    # Rows are read and written through the refs in aligned groups (a
    # packed bf16 tile is 16 rows), then stepped one [1, C] row at a time
    # on values: Mosaic lowers neither a dynamic_slice of a value nor an
    # unaligned dynamic row of a ref.
    def group(g, h):
        rows = pl.ds(pl.multiple_of(g * rows_per_group, rows_per_group),
                     rows_per_group)
        x = x_ref[0, rows, :].astype(jnp.float32)       # [R, C]
        ga = ga_ref[0, rows, :].astype(jnp.float32)
        gi = gi_ref[0, rows, :].astype(jnp.float32)
        log_at = c_const * la * ga                      # <= 0
        at = jnp.exp(log_at)
        bt = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_at), 0.0)) * (
            gi * x)
        out = []
        for t in range(rows_per_group):
            h = at[t:t + 1] * h + bt[t:t + 1]
            out.append(h)
        h_ref[0, rows, :] = jnp.concatenate(out, axis=0).astype(h_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk // rows_per_group, group, state_scr[...])
    state_scr[...] = h

    @pl.when(cj == n_chunks - 1)
    def _finish():
        state_out_ref[0] = h


def rglru_pallas(
    x: jnp.ndarray,          # [B, S, C]
    gate_a: jnp.ndarray,     # [B, S, C]
    gate_i: jnp.ndarray,     # [B, S, C]
    log_a: jnp.ndarray,      # [C]
    *,
    initial_state: Optional[jnp.ndarray] = None,
    c: float = 8.0,
    chunk: int = 256,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if initial_state is not None:
        raise ValueError("rglru_pallas starts from a zero state; resumed "
                         "scans go through ops.rglru")
    B, S, C = x.shape
    Q = min(chunk, S)
    if S % Q:
        Q = S
    nc = S // Q

    kernel = functools.partial(_kernel, chunk=Q, n_chunks=nc, c_const=c,
                               rows_per_group=math.gcd(Q, 16))
    h, state = pl.pallas_call(
        kernel,
        grid=(B, nc),
        in_specs=[
            pl.BlockSpec((1, Q, C), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, Q, C), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, Q, C), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, C), lambda b, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, C), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, 1, C), lambda b, j: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, C), x.dtype),
            jax.ShapeDtypeStruct((B, 1, C), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, C), jnp.float32)],
        interpret=interpret,
    )(x, gate_a, gate_i, log_a.reshape(1, C))
    return h, state[:, 0]
