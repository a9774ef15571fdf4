"""Mixture-of-Experts FFN: top-k routing, drop-free grouped products over
the experts on one device (ops.moe_apply), capacity dispatch on a mesh
(expert-parallel for a sequence, per batch row for decode), optional shared
experts (DeepSeek-style), auxiliary load-balance loss.

Expert weights are stacked ``[E, ...]`` so the expert axis shards over the
``model`` mesh axis (expert parallelism).  The layer runs under
``jax.named_scope("moe")`` (router, dispatch, grouped products, combine and
shared experts), so that a profile can time it apart from the rest of a
layer.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.kernels import ops

from .layers import DEFAULT_COMPUTE_DTYPE, apply_mlp, cast, mlp_init


def moe_init(key, d_model: int, d_ff: int, cfg: MoEConfig) -> Dict:
    ks = jax.random.split(key, 5)
    E = cfg.n_experts
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(d_ff)
    p = {
        "router": jax.random.normal(ks[0], (d_model, E)) * s_in,
        "gate_w": jax.random.normal(ks[1], (E, d_model, d_ff)) * s_in,
        "up_w": jax.random.normal(ks[2], (E, d_model, d_ff)) * s_in,
        "down_w": jax.random.normal(ks[3], (E, d_ff, d_model)) * s_out,
    }
    if cfg.n_shared:
        p["shared"] = mlp_init(ks[4], d_model, cfg.n_shared * d_ff, "swiglu")
    return p


def _expert_swiglu(buf, gw, uw, dw, dtype):
    """Each expert's SwiGLU over its capacity buffer ``buf [E, C, D]``."""
    h = jnp.einsum("ecd,edf->ecf", buf, cast(gw, dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, cast(uw, dtype))
    h = jax.nn.silu(h.astype(jnp.float32)).astype(dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, cast(dw, dtype))


def _moe_rows(p: Dict, x, idx, gate, cfg: MoEConfig, dtype):
    """Capacity dispatch within each batch row, vmapped over the rows: a
    form that GSPMD partitions over a mesh's batch axis (decode on a mesh),
    where flattening the rows would have it replicate the activations.
    A row's capacity is ``capacity_factor * top_k * S / E`` and at least 1,
    so a row of one token (decode) keeps its ``top_k`` assignments, which
    name distinct experts."""
    S = x.shape[1]
    cap = max(1, int(cfg.capacity_factor * cfg.top_k * S // cfg.n_experts))

    def row(xr, ir, gr):
        buf, meta = ops.moe_dispatch(xr, ir, gr, cfg.n_experts, cap)
        y = _expert_swiglu(buf, p["gate_w"], p["up_w"], p["down_w"], dtype)
        return ops.moe_combine(y, meta, S)

    return jax.vmap(row)(x, idx, gate)


def _moe_shard_map(p: Dict, x, idx, gate, cfg: MoEConfig, shard, dtype):
    """Expert-parallel MoE via shard_map: local routing + capacity dispatch,
    one all_to_all to the expert shards over ``model``, dense expert
    matmuls (weights FSDP-gathered over ``data``), one all_to_all back.

    This is the GShard/Switch pattern: collective volume per layer is
    ~2 * k * activations + expert-weight gather, deterministic and
    overlappable — the global-view sort/scatter formulation measured
    ~90 GiB/device/layer of SPMD-inserted all-reduce on dbrx
    (EXPERIMENTS.md §Perf).
    """
    mesh = shard.mesh
    model_axis = shard.model_axis
    batch_axes = shard.batch_axes
    n_model = mesh.shape[model_axis]
    E = cfg.n_experts
    assert E % n_model == 0, (E, n_model)
    B, S, D = x.shape
    b_ax = batch_axes if (batch_axes and
                          B % shard._axis_size(batch_axes) == 0) else None
    s_ax = model_axis if S % n_model == 0 else None
    data_axis = "data" if "data" in mesh.axis_names else None
    w_data = (data_axis if (data_axis and
                            D % mesh.shape[data_axis] == 0) else None)

    def local(x_l, idx_l, gate_l, gw, uw, dw):
        B_l, S_l, _ = x_l.shape
        T = B_l * S_l
        cap = max(1, int(cfg.capacity_factor * cfg.top_k * T // E))
        buf, meta = ops.moe_dispatch(
            x_l.reshape(T, D), idx_l.reshape(T, -1), gate_l.reshape(T, -1),
            E, cap)
        # tokens -> expert shards (split E, concat capacity)
        buf = jax.lax.all_to_all(buf, model_axis, split_axis=0,
                                 concat_axis=1, tiled=True)
        if w_data is not None:
            gw = jax.lax.all_gather(gw, w_data, axis=1, tiled=True)
            uw = jax.lax.all_gather(uw, w_data, axis=1, tiled=True)
            dw = jax.lax.all_gather(dw, w_data, axis=2, tiled=True)
        y = _expert_swiglu(buf, gw, uw, dw, dtype)
        # expert outputs -> back to token shards
        y = jax.lax.all_to_all(y, model_axis, split_axis=1, concat_axis=0,
                               tiled=True)
        out = ops.moe_combine(y, meta, T)
        return out.reshape(B_l, S_l, D)

    act_spec = P(b_ax, s_ax, None)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(act_spec, act_spec, act_spec,
                  P(model_axis, w_data, None),
                  P(model_axis, w_data, None),
                  P(model_axis, None, w_data)),
        out_specs=act_spec,
        check_vma=False)
    return fn(x, idx, gate, p["gate_w"], p["up_w"], p["down_w"])


@jax.named_scope("moe")
def moe_apply(
    p: Dict,
    x: jnp.ndarray,              # [B, S, D]
    cfg: MoEConfig,
    *,
    shard=None,
    dtype=DEFAULT_COMPUTE_DTYPE,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (out [B,S,D], aux load-balance loss scalar).

    Gates are the top-k softmax scores, renormalised to sum to one where
    ``cfg.norm_topk_prob``, else scaled by ``cfg.routed_scaling_factor`` (as
    DeepSeek-V2's gate does).  On one device every (token, expert)
    assignment of the batch is computed (``ops.moe_apply``), from the
    whole stack of the stage's experts where ``p["layer"]`` indexes it
    (``lm._hold_experts``).  On a mesh a sequence goes through the
    expert-parallel ``_moe_shard_map`` and one token a row (decode) through
    ``_moe_rows``: both dispatch into per-expert capacity, which can drop a
    sequence's assignments and keeps every one of a single token's.
    """
    B, S, D = x.shape
    logits = (x @ cast(p["router"], dtype)).astype(jnp.float32)   # [B,S,E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    else:
        gate = gate * cfg.routed_scaling_factor

    on_mesh = shard is not None and getattr(shard, "mesh", None) is not None
    if on_mesh and S > 1:
        y = _moe_shard_map(p, x, idx.astype(jnp.int32),
                           gate.astype(dtype), cfg, shard, dtype)
    elif on_mesh:
        y = _moe_rows(p, x, idx.astype(jnp.int32), gate.astype(dtype), cfg,
                      dtype)
    else:
        y = ops.moe_apply(
            x.reshape(B * S, D), p["gate_w"], p["up_w"], p["down_w"],
            idx.reshape(B * S, -1).astype(jnp.int32),
            gate.reshape(B * S, -1), layer=p.get("layer"),
            dtype=dtype).reshape(B, S, D)
    if "shared" in p:
        y = y + apply_mlp(p["shared"], x, "swiglu", dtype)

    # Switch-style aux loss: E * sum_e f_e * P_e
    E = cfg.n_experts
    me = probs.reshape(-1, E).mean(axis=0)                  # mean prob/expert
    one_hot = jax.nn.one_hot(idx[..., 0].reshape(-1), E, dtype=jnp.float32)
    ce = one_hot.mean(axis=0)
    aux = E * jnp.sum(me * ce)
    return y, aux
