"""Jit-friendly op wrappers used by the model zoo.

Each op has up to three interchangeable implementations:

* ``backend="ref"``    — the pure-jnp oracle from :mod:`repro.kernels.ref`
  (quadratic / sequential; ground truth),
* ``backend="xla"``    — the efficient XLA formulation used by the
  distributed train/serve paths (online-softmax KV-chunk streaming for
  attention, chunked SSD, associative-scan RG-LRU, sorted grouped-product
  MoE),
* ``backend="pallas"`` — the Pallas TPU kernels (see flash_attention.py,
  ssd_scan.py, ...), compiled for the TPU; the tests validate them on CPU
  with ``interpret=True``.

All implementations are tested against the reference over shape/dtype
sweeps in ``tests/test_kernels.py``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref

NEG_INF = -1e30


# ============================================================== attention
def flash_attention(
    q: jnp.ndarray,          # [B, Sq, H, D]
    k: jnp.ndarray,          # [B, Sk, KV, D]
    v: jnp.ndarray,          # [B, Sk, KV, Dv]
    *,
    mask_kind: str = "causal",        # causal|window|none
    window: int = 0,
    q_offset=0,
    kv_chunk: int = 512,
    scale: Optional[float] = None,
    backend: str = "xla",
) -> jnp.ndarray:
    """Streaming (online-softmax) attention.  Returns [B, Sq, H, Dv]."""
    if backend == "ref":
        return ref.attention(q, k, v, _full_mask(q, k, mask_kind, window,
                                                 q_offset), scale)
    if backend == "pallas":
        from .flash_attention import flash_attention_pallas
        return flash_attention_pallas(q, k, v, mask_kind=mask_kind,
                                      window=window, q_offset=q_offset,
                                      scale=scale)
    return _flash_xla(q, k, v, mask_kind, window, q_offset, kv_chunk, scale)


def _full_mask(q, k, mask_kind, window, q_offset):
    from repro.models.layers import causal_mask, window_mask
    Sq, Sk = q.shape[1], k.shape[1]
    if mask_kind == "causal":
        return causal_mask(Sq, Sk, q_offset)
    if mask_kind == "window":
        return window_mask(Sq, Sk, q_offset, window)
    return None


def _chunk_mask(mask_kind, window, q_pos, k_pos, Sk):
    valid = k_pos < Sk
    if mask_kind == "causal":
        valid = valid & (k_pos <= q_pos)
    elif mask_kind == "window":
        valid = valid & (k_pos <= q_pos) & (k_pos > q_pos - window)
    return valid  # [Sq, C]


def _flash_fwd_core(qf, kc, vc, q_pos, mask_kind, window, kv_chunk, Sk):
    """Online-softmax forward over stacked KV chunks.

    qf: [B,Sq,KV,G,D] (pre-scaled fp32); kc/vc: [nc,B,C,KV,D*].
    Returns (out fp32 [B,Sq,KV,G,Dv], lse [B,Sq,KV,G])."""
    B, Sq, KV, G, D = qf.shape
    Dv = vc.shape[-1]
    n_chunks = kc.shape[0]

    def body(carry, inp):
        m, lsum, acc = carry
        idx, kci, vci = inp
        logits = jnp.einsum("bqhgd,bkhd->bqhgk", qf, kci)
        k_pos = (idx * kv_chunk + jnp.arange(kv_chunk))[None, :]
        valid = _chunk_mask(mask_kind, window, q_pos, k_pos, Sk)
        logits = jnp.where(valid[None, :, None, None, :], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        lsum = lsum * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bqhgk,bkhd->bqhgd", p, vci)
        return (m_new, lsum, acc), None

    init = (jnp.full((B, Sq, KV, G), NEG_INF, jnp.float32),
            jnp.zeros((B, Sq, KV, G), jnp.float32),
            jnp.zeros((B, Sq, KV, G, Dv), jnp.float32))
    (m, lsum, acc), _ = jax.lax.scan(body, init,
                                  (jnp.arange(n_chunks), kc, vc))
    lsum = jnp.maximum(lsum, 1e-30)
    out = acc / lsum[..., None]
    lse = m + jnp.log(lsum)
    return out, lse


@functools.lru_cache(maxsize=None)
def _flash_custom(mask_kind: str, window: int, kv_chunk: int, scale: float):
    """Flash attention with a custom VJP: the backward pass recomputes each
    KV chunk's probabilities from the saved logsumexp instead of letting
    scan-autodiff stash every chunk iteration's online-softmax carries
    (which measured tens of GiB on the 32k-context cells — EXPERIMENTS.md
    §Perf)."""

    def _prep(q, k, v):
        B, Sq, H, D = q.shape
        Sk, KV = k.shape[1], k.shape[2]
        Dv = v.shape[-1]
        G = H // KV
        chunk = min(kv_chunk, Sk)
        n_chunks = -(-Sk // chunk)
        pad = n_chunks * chunk - Sk
        qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, KV, G, D)
        kf = k.astype(jnp.float32)
        vf = v.astype(jnp.float32)
        if pad:
            kf = jnp.pad(kf, ((0, 0), (0, pad), (0, 0), (0, 0)))
            vf = jnp.pad(vf, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kc = kf.reshape(B, n_chunks, chunk, KV, D).transpose(1, 0, 2, 3, 4)
        vc = vf.reshape(B, n_chunks, chunk, KV, Dv).transpose(1, 0, 2, 3, 4)
        return qf, kc, vc, chunk, n_chunks, Sk, pad

    @jax.custom_vjp
    def fn(q, k, v, q_offset):
        qf, kc, vc, chunk, _, Sk, _ = _prep(q, k, v)
        q_pos = (jnp.asarray(q_offset) + jnp.arange(q.shape[1]))[:, None]
        out, _ = _flash_fwd_core(qf, kc, vc, q_pos, mask_kind, window,
                                 chunk, Sk)
        B, Sq, KV, G, Dv = out.shape
        return out.reshape(B, Sq, KV * G, Dv).astype(q.dtype)

    def fwd(q, k, v, q_offset):
        qf, kc, vc, chunk, _, Sk, _ = _prep(q, k, v)
        q_pos = (jnp.asarray(q_offset) + jnp.arange(q.shape[1]))[:, None]
        out, lse = _flash_fwd_core(qf, kc, vc, q_pos, mask_kind, window,
                                   chunk, Sk)
        B, Sq, KV, G, Dv = out.shape
        return (out.reshape(B, Sq, KV * G, Dv).astype(q.dtype),
                (q, k, v, q_offset, out, lse))

    def bwd(res, g):
        q, k, v, q_offset, out, lse = res
        qf, kc, vc, chunk, n_chunks, Sk, pad = _prep(q, k, v)
        B, Sq, H, D = q.shape
        KV = k.shape[2]
        G = H // KV
        Dv = v.shape[-1]
        q_pos = (jnp.asarray(q_offset) + jnp.arange(Sq))[:, None]
        do = g.astype(jnp.float32).reshape(B, Sq, KV, G, Dv)
        # D_i = rowsum(dO * O)
        delta = jnp.sum(do * out, axis=-1)                  # [B,Sq,KV,G]

        def body(dq, inp):
            idx, kci, vci = inp
            logits = jnp.einsum("bqhgd,bkhd->bqhgk", qf, kci)
            k_pos = (idx * chunk + jnp.arange(chunk))[None, :]
            valid = _chunk_mask(mask_kind, window, q_pos, k_pos, Sk)
            p = jnp.exp(logits - lse[..., None])            # [B,Sq,KV,G,C]
            p = jnp.where(valid[None, :, None, None, :], p, 0.0)
            dv_c = jnp.einsum("bqhgk,bqhgd->bkhd", p, do)
            dp = jnp.einsum("bqhgd,bkhd->bqhgk", do, vci)
            ds = p * (dp - delta[..., None])
            dq = dq + jnp.einsum("bqhgk,bkhd->bqhgd", ds, kci)
            dk_c = jnp.einsum("bqhgk,bqhgd->bkhd", ds, qf)
            return dq, (dk_c, dv_c)

        dq0 = jnp.zeros_like(qf)
        dq, (dk_c, dv_c) = jax.lax.scan(
            body, dq0, (jnp.arange(n_chunks), kc, vc))
        dq = (dq * scale).reshape(B, Sq, H, D).astype(q.dtype)
        dk = dk_c.transpose(1, 0, 2, 3, 4).reshape(B, -1, KV, D)
        dv = dv_c.transpose(1, 0, 2, 3, 4).reshape(B, -1, KV, Dv)
        if pad:
            dk = dk[:, :Sk]
            dv = dv[:, :Sk]
        return dq, dk.astype(k.dtype), dv.astype(v.dtype), None

    fn.defvjp(fwd, bwd)
    return fn


def _flash_xla(q, k, v, mask_kind, window, q_offset, kv_chunk, scale):
    D = q.shape[-1]
    scale = scale if scale is not None else D ** -0.5
    fn = _flash_custom(mask_kind, int(window), int(kv_chunk), float(scale))
    return fn(q, k, v, jnp.asarray(q_offset))


def decode_attention(
    q: jnp.ndarray,          # [B, H, D]
    k_cache: jnp.ndarray,    # [B, S, KV, D]
    v_cache: jnp.ndarray,    # [B, S, KV, Dv]
    length: jnp.ndarray,     # [B]
    *,
    scale: Optional[float] = None,
    backend: str = "xla",
) -> jnp.ndarray:
    """Single-token decode attention against a padded KV cache. [B, H, Dv].

    The XLA path materializes logits [B, H, S] (tiny) and lets SPMD insert
    the cross-shard softmax collectives when S is sharded (flash-decode
    style distributed softmax).
    """
    if backend == "ref":
        return ref.decode_attention(q, k_cache, v_cache, length, scale)
    if backend == "pallas":
        from .decode_attention import decode_attention_pallas
        return decode_attention_pallas(q, k_cache, v_cache, length, scale=scale)
    B, H, D = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = scale if scale is not None else D ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(B, KV, G, D)
    logits = jnp.einsum("bhgd,bshd->bhgs", qf, k_cache.astype(jnp.float32))
    valid = jnp.arange(S)[None] < length[:, None]
    logits = jnp.where(valid[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", probs, v_cache.astype(jnp.float32))
    return out.reshape(B, H, -1).astype(q.dtype)


# ==================================================================== SSD
def ssd(
    x: jnp.ndarray,          # [B, S, H, P]
    dt: jnp.ndarray,         # [B, S, H]
    A: jnp.ndarray,          # [H]
    Bmat: jnp.ndarray,       # [B, S, G, N]
    Cmat: jnp.ndarray,       # [B, S, G, N]
    *,
    chunk: int = 128,
    initial_state: Optional[jnp.ndarray] = None,
    backend: str = "xla",
) -> tuple:
    """Mamba-2 SSD (state-space duality) mixer: (y, final_state)."""
    if backend == "ref":
        return ref.ssd_scan(x, dt, A, Bmat, Cmat, initial_state)
    if backend == "pallas" and initial_state is None:
        from .ssd_scan import ssd_pallas
        return ssd_pallas(x, dt, A, Bmat, Cmat, chunk=chunk)
    # Resumed scans take the XLA path: the kernel starts from zero.
    return _ssd_chunked_xla(x, dt, A, Bmat, Cmat, chunk, initial_state)


def _ssd_chunked_xla(x, dt, A, Bmat, Cmat, chunk, initial_state):
    """Chunked SSD: quadratic intra-chunk (attention-like) + linear
    inter-chunk state recurrence — the Mamba-2 paper's algorithm."""
    Bsz, S, H, P = x.shape
    G, N = Bmat.shape[2], Bmat.shape[3]
    rep = H // G
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q

    xf = x.astype(jnp.float32).reshape(Bsz, nc, Q, H, P)
    dtf = dt.astype(jnp.float32).reshape(Bsz, nc, Q, H)
    Af = A.astype(jnp.float32)
    Bf = Bmat.astype(jnp.float32).reshape(Bsz, nc, Q, G, N)
    Cf = Cmat.astype(jnp.float32).reshape(Bsz, nc, Q, G, N)
    Bh = jnp.repeat(Bf, rep, axis=3)                     # [B,nc,Q,H,N]
    Ch = jnp.repeat(Cf, rep, axis=3)

    dA = dtf * Af[None, None, None, :]                   # [B,nc,Q,H] (<=0)
    cum = jnp.cumsum(dA, axis=2)                         # within-chunk cumsum
    total = cum[:, :, -1:, :]                            # [B,nc,1,H]

    # --- intra-chunk (attention-like) ------------------------------------
    # decay[t, s] = exp(cum_t - cum_s) for s <= t.  Mask inside the exponent:
    # for s > t the difference is positive and exp() overflows to inf, and
    # inf * 0 = NaN if masked after the fact.
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # [B,nc,Q,Q,H]
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.exp(jnp.where(tri[None, None, :, :, None], diff, -jnp.inf))
    scores = jnp.einsum("bcqhn,bckhn->bcqkh", Ch, Bh)    # [B,nc,Q,Q,H]
    L = scores * decay
    y_intra = jnp.einsum("bcqkh,bckh,bckhp->bcqhp", L, dtf, xf)

    # --- chunk states ------------------------------------------------------
    # state_c = sum_s exp(total - cum_s) dt_s x_s (x) B_s   -> [B,nc,H,P,N]
    w = jnp.exp(total - cum) * dtf                       # [B,nc,Q,H]
    chunk_state = jnp.einsum("bcqh,bcqhp,bcqhn->bchpn", w, xf, Bh)

    # --- inter-chunk recurrence -------------------------------------------
    chunk_decay = jnp.exp(total[:, :, 0, :])             # [B,nc,H]
    h0 = (initial_state.astype(jnp.float32) if initial_state is not None
          else jnp.zeros((Bsz, H, P, N), jnp.float32))

    def step(h, inp):
        dec, st = inp                                    # [B,H], [B,H,P,N]
        h_in = h                                         # state entering chunk
        h = h * dec[..., None, None] + st
        return h, h_in

    hT, h_prevs = jax.lax.scan(
        step, h0,
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(chunk_state, 1, 0)))
    h_prev = jnp.moveaxis(h_prevs, 0, 1)                 # [B,nc,H,P,N]

    # --- inter-chunk contribution ------------------------------------------
    y_inter = jnp.einsum("bcqhn,bchpn->bcqhp",
                         Ch * jnp.exp(cum)[..., None], h_prev)
    y = (y_intra + y_inter).reshape(Bsz, S, H, P)
    return y.astype(x.dtype), hT


def ssd_decode_step(
    x: jnp.ndarray,          # [B, H, P]
    dt: jnp.ndarray,         # [B, H]
    A: jnp.ndarray,          # [H]
    Bvec: jnp.ndarray,       # [B, G, N]
    Cvec: jnp.ndarray,       # [B, G, N]
    state: jnp.ndarray,      # [B, H, P, N]
) -> tuple:
    """Single-token SSD update: (y [B,H,P], new_state)."""
    H = x.shape[1]
    G = Bvec.shape[1]
    rep = H // G
    Bh = jnp.repeat(Bvec.astype(jnp.float32), rep, axis=1)
    Ch = jnp.repeat(Cvec.astype(jnp.float32), rep, axis=1)
    dtf = dt.astype(jnp.float32)
    decay = jnp.exp(A.astype(jnp.float32)[None] * dtf)   # [B,H]
    new_state = state * decay[..., None, None] + \
        (dtf[..., None] * x.astype(jnp.float32))[..., None] * Bh[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Ch)
    return y.astype(x.dtype), new_state


# ================================================================== RG-LRU
def rglru(
    x: jnp.ndarray,          # [B, S, C]
    gate_a: jnp.ndarray,     # [B, S, C]
    gate_i: jnp.ndarray,     # [B, S, C]
    log_a: jnp.ndarray,      # [C]
    *,
    initial_state: Optional[jnp.ndarray] = None,
    c: float = 8.0,
    backend: str = "xla",
) -> tuple:
    """RG-LRU linear recurrence: (h [B,S,C], final_state [B,C])."""
    if backend == "ref":
        return ref.rglru_scan(x, gate_a, gate_i, log_a, initial_state, c)
    if backend == "pallas" and initial_state is None:
        from .rglru_scan import rglru_pallas
        return rglru_pallas(x, gate_a, gate_i, log_a, c=c)
    # Resumed scans take the XLA path: the kernel starts from zero.
    # Two-level scan: associative scan *within* chunks (parallel, O(log Q)
    # depth), lax.scan *across* chunks threading the [B, C] state.  The
    # chunk body is checkpointed so the backward pass recomputes one chunk
    # at a time instead of saving every associative-scan level over the
    # full sequence (which measured ~20 GiB/device on the 32k recurrent
    # cells — EXPERIMENTS.md §Perf).
    B, S, C = x.shape
    xf = x.astype(jnp.float32)
    log_at = c * log_a.astype(jnp.float32)[None, None, :] \
        * gate_a.astype(jnp.float32)                     # [B,S,C] <= 0
    at = jnp.exp(log_at)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_at), 0.0))
    bt = beta * (gate_i.astype(jnp.float32) * xf)

    def combine(u, w):
        a1, b1 = u
        a2, b2 = w
        return a1 * a2, b1 * a2 + b2

    Q = min(512, S)
    if S % Q:
        Q = S
    nc = S // Q
    a_c = at.reshape(B, nc, Q, C).transpose(1, 0, 2, 3)
    b_c = bt.reshape(B, nc, Q, C).transpose(1, 0, 2, 3)

    @jax.checkpoint
    def chunk(h0, inp):
        a, b = inp                                       # [B,Q,C]
        a_sc, h = jax.lax.associative_scan(combine, (a, b), axis=1)
        h = h + a_sc * h0[:, None, :]
        return h[:, -1], h

    h0 = (initial_state.astype(jnp.float32) if initial_state is not None
          else jnp.zeros((B, C), jnp.float32))
    hT, hs = jax.lax.scan(chunk, h0, (a_c, b_c))
    h = hs.transpose(1, 0, 2, 3).reshape(B, S, C)
    return h.astype(x.dtype), hT


def rglru_decode_step(x, gate_a, gate_i, log_a, state, c: float = 8.0):
    """Single-token RG-LRU update: inputs [B, C], state [B, C]."""
    log_at = c * log_a.astype(jnp.float32)[None] * gate_a.astype(jnp.float32)
    at = jnp.exp(log_at)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_at), 0.0))
    h = at * state + beta * (gate_i.astype(jnp.float32) * x.astype(jnp.float32))
    return h.astype(x.dtype), h


# ===================================================================== MoE
def moe_dispatch(
    x: jnp.ndarray,          # [T, D]
    topk_idx: jnp.ndarray,   # [T, K]
    topk_gate: jnp.ndarray,  # [T, K]
    n_experts: int,
    capacity: int,
):
    """Sort tokens into per-expert capacity buffers.

    Returns (buf [E, C, D], meta) where meta lets ``moe_combine`` scatter
    expert outputs back to token order.
    """
    T, D = x.shape
    K = topk_idx.shape[1]
    TK = T * K
    flat_e = topk_idx.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    flat_g = topk_gate.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    first = jnp.searchsorted(se, se, side="left")
    pos = jnp.arange(TK, dtype=jnp.int32) - first.astype(jnp.int32)
    keep = pos < capacity
    pos_c = jnp.where(keep, pos, 0)
    e_c = jnp.where(keep, se, 0)
    gathered = x[st] * keep[:, None].astype(x.dtype)
    buf = jnp.zeros((n_experts, capacity, D), x.dtype)
    buf = buf.at[e_c, pos_c].add(gathered, mode="drop")
    return buf, (e_c, pos_c, st, (sg * keep).astype(x.dtype))


def moe_combine(y: jnp.ndarray, meta, T: int) -> jnp.ndarray:
    """Inverse of ``moe_dispatch``: weighted scatter back to [T, D]."""
    e_c, pos_c, st, w = meta
    contrib = y[e_c, pos_c] * w[:, None]
    return jnp.zeros((T, y.shape[-1]), y.dtype).at[st].add(
        contrib, mode="drop")


#: megablox ``gmm`` tiles, (rows, contraction, output), by the rows of a
#: call: a decode step's few (rows None: its rows in one tile) and a
#: prefill's many; the fastest of those ``chipbench/grouped_products.py``
#: times on a v5e at DeepSeek-V2-Lite's expert widths.
GMM_TILING_FEW = (None, 512, 1408)
GMM_TILING_MANY = (256, 2048, 512)
#: Rows up to which a grouped product counts as few.
GMM_FEW_ROWS = 512


def grouped_product(lhs: jnp.ndarray, rhs: jnp.ndarray, sizes: jnp.ndarray,
                    *, tiling: Optional[Tuple[Optional[int], int, int]] = None
                    ) -> jnp.ndarray:
    """Rows ``lhs[m, k]``, sorted by group, times their group's matrix of
    ``rhs[G, k, n]``; ``sizes[G]`` counts each group's rows.  On a TPU the
    megablox kernel (``gmm``), which reads the matrices of the groups that
    hold rows and no other, with ``tiling`` (rows, contraction, output;
    rows None: the call's rows in one tile), by default the one for the
    call's rows above; elsewhere ``jax.lax.ragged_dot``."""
    if jax.default_backend() != "tpu":
        return jax.lax.ragged_dot(lhs, rhs, sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = lhs.shape
    if tiling is None:
        tiling = GMM_TILING_FEW if m <= GMM_FEW_ROWS else GMM_TILING_MANY
    tm, tk, tn = tiling
    tm = tm or -(-m // 16) * 16
    pad = -m % tm
    out = gmm(jnp.pad(lhs, ((0, pad), (0, 0))), rhs, sizes,
              preferred_element_type=lhs.dtype,
              tiling=(tm, min(tk, k), min(tn, rhs.shape[2])))
    return out[:m]


def moe_apply(
    x: jnp.ndarray,          # [T, D] flattened tokens
    gate_w: jnp.ndarray,     # [E, D, F], or [L, E, D, F] with ``layer``
    up_w: jnp.ndarray,       # [E, D, F], or [L, E, D, F]
    down_w: jnp.ndarray,     # [E, F, D], or [L, E, F, D]
    topk_idx: jnp.ndarray,   # [T, K] int32
    topk_gate: jnp.ndarray,  # [T, K] float
    *,
    layer: Optional[jnp.ndarray] = None,
    dtype=jnp.bfloat16,
) -> jnp.ndarray:
    """Drop-free routed SwiGLU: the ``T*K`` (token, expert) assignments are
    sorted by expert over the whole batch, each expert's rows form one group
    of a grouped product (:func:`grouped_product`), and each token's ``K``
    outputs are summed with its gates.  Every assignment is computed at any
    load, all tokens on one expert included.

    With ``layer``, the weights are a stack of ``L`` layers' experts and
    the products read layer ``layer``'s from it in place: the stack is
    ``L*E`` groups, of which only that layer's hold rows (a slice of the
    stack would be copied for the kernel)."""
    T, D = x.shape
    K = topk_idx.shape[1]
    if layer is not None:
        L, E = gate_w.shape[:2]
        gate_w, up_w, down_w = (w.reshape((L * E,) + w.shape[2:])
                                for w in (gate_w, up_w, down_w))
        topk_idx = topk_idx + layer * E
    flat_e = topk_idx.reshape(-1)                        # [TK]
    order = jnp.argsort(flat_e, stable=True)
    rows = x[order // K].astype(dtype)                   # [TK, D] by group
    sizes = jnp.bincount(flat_e, length=gate_w.shape[0]).astype(jnp.int32)

    h = grouped_product(rows, gate_w.astype(dtype), sizes)
    u = grouped_product(rows, up_w.astype(dtype), sizes)
    h = jax.nn.silu(h.astype(jnp.float32)).astype(dtype) * u
    y = grouped_product(h, down_w.astype(dtype), sizes)

    y = y[jnp.argsort(order)].reshape(T, K, D)           # token order
    out = jnp.einsum("tkd,tk->td", y.astype(jnp.float32),
                     topk_gate.astype(jnp.float32))
    return out.astype(x.dtype)
