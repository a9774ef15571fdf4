"""Kernel validation: XLA formulations and Pallas TPU kernels (interpret
mode) against the pure-jnp oracles, swept over shapes and dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rglru_scan import rglru_pallas
from repro.kernels.ssd_scan import ssd_pallas
from repro.models.layers import causal_mask, window_mask

TOL = dict(rtol=2e-3, atol=2e-3)
TOL32 = dict(rtol=1e-5, atol=1e-5)


def _mask(kind, sq, sk, window):
    if kind == "causal":
        return causal_mask(sq, sk, 0)
    if kind == "window":
        return window_mask(sq, sk, 0, window)
    return None


# ----------------------------------------------------------- attention
ATTN_SWEEP = [
    # (B, Sq, Sk, H, KV, D, mask_kind, window, dtype)
    (1, 8, 8, 2, 2, 8, "causal", 0, jnp.float32),
    (2, 16, 16, 4, 2, 16, "causal", 0, jnp.float32),
    (2, 16, 24, 4, 1, 8, "none", 0, jnp.float32),
    (1, 24, 24, 8, 4, 32, "window", 7, jnp.float32),
    (2, 16, 16, 4, 4, 16, "causal", 0, jnp.bfloat16),
    (1, 32, 16, 2, 2, 64, "causal", 0, jnp.float32),   # Sq > Sk
]


@pytest.mark.parametrize("case", ATTN_SWEEP)
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_attention_matches_oracle(case, impl):
    B, Sq, Sk, H, KV, D, kind, window, dtype = case
    ks = jax.random.split(jax.random.PRNGKey(hash(case) % 2**31), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, KV, D), dtype)
    want = np.asarray(
        ref.attention(q, k, v, _mask(kind, Sq, Sk, window)), np.float32)
    if impl == "xla":
        got = ops.flash_attention(q, k, v, mask_kind=kind, window=window,
                                  kv_chunk=7)
    else:
        got = flash_attention_pallas(q, k, v, mask_kind=kind, window=window,
                                     block_q=8, block_k=8, interpret=True)
    tol = TOL if dtype == jnp.bfloat16 else TOL32
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=tol["rtol"] * 10, atol=tol["atol"] * 10)


def test_flash_gradients_match_oracle():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    B, Sq, Sk, H, KV, D = 2, 12, 12, 4, 2, 16
    q = jax.random.normal(ks[0], (B, Sq, H, D))
    k = jax.random.normal(ks[1], (B, Sk, KV, D))
    v = jax.random.normal(ks[2], (B, Sk, KV, D))
    mask = causal_mask(Sq, Sk, 0)

    g_ref = jax.grad(lambda *a: (ref.attention(*a, mask) ** 2).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    g_xla = jax.grad(
        lambda *a: (ops.flash_attention(*a, mask_kind="causal",
                                        kv_chunk=5) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_xla):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-4)


DECODE_SWEEP = [
    (1, 8, 2, 2, 8, jnp.float32),
    (2, 32, 8, 4, 16, jnp.float32),
    (3, 17, 4, 1, 32, jnp.float32),
    (2, 16, 4, 4, 16, jnp.bfloat16),
]


@pytest.mark.parametrize("case", DECODE_SWEEP)
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_decode_attention_matches_oracle(case, impl):
    B, S, H, KV, D, dtype = case
    ks = jax.random.split(jax.random.PRNGKey(hash(case) % 2**31), 4)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    kc = jax.random.normal(ks[1], (B, S, KV, D), dtype)
    vc = jax.random.normal(ks[2], (B, S, KV, D), dtype)
    length = jax.random.randint(ks[3], (B,), 1, S + 1)
    want = np.asarray(ref.decode_attention(q, kc, vc, length), np.float32)
    if impl == "xla":
        got = ops.decode_attention(q, kc, vc, length)
    else:
        got = decode_attention_pallas(q, kc, vc, length, block_k=8,
                                      interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=2e-2 if dtype == jnp.bfloat16 else 1e-4)


# ----------------------------------------------------------------- SSD
SSD_SWEEP = [
    # (B, S, H, P, G, N, chunk, dtype)
    (1, 16, 2, 4, 1, 8, 8, jnp.float32),
    (2, 32, 4, 8, 2, 16, 8, jnp.float32),
    (1, 24, 2, 8, 1, 4, 12, jnp.float32),
    (2, 32, 4, 8, 1, 16, 16, jnp.bfloat16),
]


@pytest.mark.parametrize("case", SSD_SWEEP)
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_ssd_matches_oracle(case, impl):
    B, S, H, P, G, N, chunk, dtype = case
    ks = jax.random.split(jax.random.PRNGKey(hash(case) % 2**31), 5)
    x = (jax.random.normal(ks[0], (B, S, H, P)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = (jax.random.normal(ks[3], (B, S, G, N)) * 0.3).astype(dtype)
    Cm = (jax.random.normal(ks[4], (B, S, G, N)) * 0.3).astype(dtype)
    y_ref, h_ref = ref.ssd_scan(x, dt, A, Bm, Cm)
    if impl == "xla":
        y, h = ops.ssd(x, dt, A, Bm, Cm, chunk=chunk)
    else:
        y, h = ssd_pallas(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               rtol=tol, atol=tol)


def test_ssd_with_initial_state():
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    B, S, H, P, G, N = 2, 16, 2, 4, 1, 8
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.3
    h0 = jax.random.normal(ks[5], (B, H, P, N)) * 0.2
    y_ref, h_ref = ref.ssd_scan(x, dt, A, Bm, Cm, h0)
    y, h = ops.ssd(x, dt, A, Bm, Cm, chunk=8, initial_state=h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_pallas_scans_refuse_initial_state_and_ops_resumes_them():
    """The kernels start from a zero state: a direct call with a state
    raises, and ``ops`` hands a resumed scan to the XLA path explicitly."""
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    B, S, H, P, G, N = 1, 16, 2, 4, 1, 8
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.3
    h0 = jax.random.normal(ks[5], (B, H, P, N)) * 0.2
    with pytest.raises(ValueError, match="zero state"):
        ssd_pallas(x, dt, A, Bm, Cm, chunk=8, initial_state=h0,
                   interpret=True)
    y_ref, _ = ref.ssd_scan(x, dt, A, Bm, Cm, h0)
    y, _ = ops.ssd(x, dt, A, Bm, Cm, chunk=8, initial_state=h0,
                   backend="pallas")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)

    C = 8
    xr = jax.random.normal(ks[0], (B, S, C))
    ga = jax.nn.sigmoid(jax.random.normal(ks[1], (B, S, C)))
    gi = jax.nn.sigmoid(jax.random.normal(ks[2], (B, S, C)))
    la = -jax.nn.softplus(jax.random.normal(ks[3], (C,))) * 0.1
    s0 = jax.random.normal(ks[4], (B, C))
    with pytest.raises(ValueError, match="zero state"):
        rglru_pallas(xr, ga, gi, la, initial_state=s0, interpret=True)
    h_ref, _ = ref.rglru_scan(xr, ga, gi, la, s0)
    h, _ = ops.rglru(xr, ga, gi, la, initial_state=s0, backend="pallas")
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               rtol=1e-4, atol=1e-4)


def test_ssd_decode_step_consistent_with_scan():
    """Decoding token-by-token must equal the full-sequence scan."""
    ks = jax.random.split(jax.random.PRNGKey(6), 5)
    B, S, H, P, G, N = 1, 8, 2, 4, 1, 8
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.3
    y_ref, _ = ref.ssd_scan(x, dt, A, Bm, Cm)
    h = jnp.zeros((B, H, P, N))
    outs = []
    for t in range(S):
        y_t, h = ops.ssd_decode_step(x[:, t], dt[:, t], A, Bm[:, t],
                                     Cm[:, t], h)
        outs.append(y_t)
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


# -------------------------------------------------------------- RG-LRU
RGLRU_SWEEP = [
    (1, 16, 4, jnp.float32),
    (2, 48, 12, jnp.float32),
    (2, 1024, 4, jnp.float32),       # multi-chunk path
    (2, 32, 8, jnp.bfloat16),
]


@pytest.mark.parametrize("case", RGLRU_SWEEP)
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_rglru_matches_oracle(case, impl):
    B, S, C, dtype = case
    ks = jax.random.split(jax.random.PRNGKey(hash(case) % 2**31), 4)
    x = (jax.random.normal(ks[0], (B, S, C)) * 0.5).astype(dtype)
    ga = jax.nn.sigmoid(jax.random.normal(ks[1], (B, S, C))).astype(dtype)
    gi = jax.nn.sigmoid(jax.random.normal(ks[2], (B, S, C))).astype(dtype)
    la = -jax.nn.softplus(jax.random.normal(ks[3], (C,))) * 0.1
    h_ref, hT_ref = ref.rglru_scan(x, ga, gi, la)
    if impl == "xla":
        h, hT = ops.rglru(x, ga, gi, la)
    else:
        h, hT = rglru_pallas(x, ga, gi, la, chunk=16, interpret=True)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(h, np.float32),
                               np.asarray(h_ref, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hT_ref),
                               rtol=tol, atol=tol)


# ----------------------------------------------------------------- MoE
def test_moe_no_drop_matches_dense_oracle():
    ks = jax.random.split(jax.random.PRNGKey(8), 6)
    T, D, E, F, K = 64, 16, 4, 32, 2
    x = jax.random.normal(ks[0], (T, D))
    gw = jax.random.normal(ks[1], (E, D, F)) * 0.1
    uw = jax.random.normal(ks[2], (E, D, F)) * 0.1
    dw = jax.random.normal(ks[3], (E, F, D)) * 0.1
    probs = jax.nn.softmax(jax.random.normal(ks[4], (T, E)))
    gate, idx = jax.lax.top_k(probs, K)
    gate = gate / gate.sum(-1, keepdims=True)
    dense = jnp.zeros((T, E)).at[jnp.arange(T)[:, None], idx].set(gate)
    want = ref.moe_dense(x, gw, uw, dw, dense)
    got = ops.moe_apply(x, gw, uw, dw, idx, gate, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("layer", [None, 2])
def test_moe_all_assignments_on_one_expert_drop_none(layer):
    """Every token routed to expert 1 first: one expert holds the whole
    batch, far past any capacity, and each assignment is still computed;
    also when the experts are read in place from a stack of layers."""
    ks = jax.random.split(jax.random.PRNGKey(10), 6)
    T, D, E, F, K, L = 48, 16, 4, 32, 2, 3
    x = jax.random.normal(ks[0], (T, D))
    ws = [jax.random.normal(k, (L, E) + s) * 0.1 for k, s in
          zip(ks[1:4], ((D, F), (D, F), (F, D)))]
    idx = jnp.stack([jnp.ones((T,), jnp.int32),
                     jax.random.randint(ks[4], (T,), 2, E)], 1)
    gate = jax.random.uniform(ks[5], (T, K))
    dense = jnp.zeros((T, E)).at[jnp.arange(T)[:, None], idx].set(gate)
    at = 2 if layer is None else layer
    want = ref.moe_dense(x, *(w[at] for w in ws), dense)
    if layer is None:
        got = ops.moe_apply(x, *(w[at] for w in ws), idx, gate,
                            dtype=jnp.float32)
    else:
        got = ops.moe_apply(x, *ws, idx, gate, layer=jnp.int32(layer),
                            dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_moe_dispatch_combine_roundtrip():
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    T, D, E, K = 32, 8, 4, 2
    x = jax.random.normal(ks[0], (T, D))
    probs = jax.nn.softmax(jax.random.normal(ks[1], (T, E)))
    gate, idx = jax.lax.top_k(probs, K)
    buf, meta = ops.moe_dispatch(x, idx, gate, E, capacity=T)
    # identity expert => combine(dispatch(x)) == sum_k gate_k * x
    out = ops.moe_combine(buf, meta, T)
    want = gate.sum(-1, keepdims=True) * x
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(
    s=st.integers(min_value=2, max_value=33),
    h=st.sampled_from([1, 2, 4]),
    kv=st.sampled_from([1, 2]),
    d=st.sampled_from([4, 8, 16]),
)
def test_attention_property_sweep(s, h, kv, d):
    if h % kv:
        h = kv
    ks = jax.random.split(jax.random.PRNGKey(s * 131 + h), 3)
    q = jax.random.normal(ks[0], (1, s, h, d))
    k = jax.random.normal(ks[1], (1, s, kv, d))
    v = jax.random.normal(ks[2], (1, s, kv, d))
    want = ref.attention(q, k, v, causal_mask(s, s, 0))
    got = ops.flash_attention(q, k, v, mask_kind="causal", kv_chunk=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
