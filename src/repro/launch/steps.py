"""Step-function builders: jitted, sharded train / prefill / decode steps.

These are the units the thread-block-style scheduler (repro.core) dispatches:
a job is N repetitions of one of these steps, so profiling the first
invocation (the paper's structural runtime prediction) predicts the job.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.configs.shapes import InputShape
from repro.data import pipeline as data_pipeline
from repro.models import lm
from repro.optim import adamw
from repro.sharding.annotate import NULL_SHARDER, Sharder, profile_for
from repro.sharding.specs import (
    batch_shardings,
    cache_shardings,
    param_shardings,
)

from .mesh import batch_axes_of


@dataclass
class StepBundle:
    """A lowered-or-lowerable step function plus its arg specs/shardings."""

    fn: object                    # jitted callable
    arg_specs: Tuple              # ShapeDtypeStructs for .lower()
    kind: str


def param_struct(cfg: ArchConfig):
    return jax.eval_shape(lambda: lm.init(cfg, jax.random.PRNGKey(0)))


def _sharder(mesh, cfg) -> object:
    if mesh is None:
        return NULL_SHARDER
    return Sharder(mesh, profile_for(cfg), batch_axes_of(mesh),
                   full_dp=cfg.moe is None)


def _replicated(mesh):
    return NamedSharding(mesh, P()) if mesh is not None else None


#: Gradient-accumulation factor per arch for the train_4k cell: splits the
#: global batch into M sequential microbatches, dividing activation-linked
#: temp memory by ~M at identical tokens/step (EXPERIMENTS.md §Perf).
TRAIN_MICROBATCHES = {
    "dbrx-132b": 4,     # MoE keeps the CP plan; memory needs grad accumulation
    "mamba2-2.7b": 4,   # only when the full-mesh DP plan cannot engage
}


def train_microbatches(cfg: ArchConfig, shape: InputShape, mesh) -> int:
    """Gradient-accumulation factor: 1 when the full-mesh DP plan engages
    (it already minimizes activation memory), else the per-arch table."""
    if mesh is None:
        return 1
    total = 1
    for n in mesh.shape.values():
        total *= n
    if cfg.moe is None and shape.global_batch % total == 0:
        return 1
    M = TRAIN_MICROBATCHES.get(cfg.arch_id, 1)
    return M if shape.global_batch % max(M, 1) == 0 else 1


# ------------------------------------------------------------------- train
def build_train_step(cfg: ArchConfig, shape: InputShape, mesh=None,
                     opt_cfg: adamw.OptConfig = adamw.OptConfig(),
                     backend: str = "xla", remat: bool = True,
                     microbatches: Optional[int] = None) -> StepBundle:
    shard = _sharder(mesh, cfg)
    M = microbatches if microbatches is not None \
        else train_microbatches(cfg, shape, mesh)
    if shape.global_batch % max(M, 1):
        M = 1

    def mb_loss(p, mb):
        return lm.loss_fn(cfg, p, mb, backend=backend, shard=shard,
                          remat=remat)

    def train_step(params, opt_state, batch):
        if M <= 1:
            (_, metrics), grads = jax.value_and_grad(
                mb_loss, has_aux=True)(params, batch)
        else:
            mbs = jax.tree.map(
                lambda a: a.reshape((M, a.shape[0] // M) + a.shape[1:]),
                batch)

            def body(acc, mb):
                (_, metrics), g = jax.value_and_grad(
                    mb_loss, has_aux=True)(params, mb)
                acc = jax.tree.map(
                    lambda x, y: x + y.astype(jnp.float32) / M, acc, g)
                return acc, metrics

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, metrics_stack = jax.lax.scan(body, zeros, mbs)
            metrics = jax.tree.map(lambda m: m[-1], metrics_stack)
        new_p, new_s, stats = adamw.update(grads, opt_state, params, opt_cfg)
        metrics = dict(metrics, **stats)
        return new_p, new_s, metrics

    p_struct = param_struct(cfg)
    o_struct = jax.eval_shape(adamw.init, p_struct)
    b_struct = data_pipeline.batch_spec(cfg, shape)

    if mesh is not None:
        p_sh = param_shardings(p_struct, mesh)
        o_sh = {"m": p_sh, "v": p_sh, "step": _replicated(mesh)}
        b_sh = batch_shardings(b_struct, mesh, cfg, profile_for(cfg))
        fn = jax.jit(train_step,
                     in_shardings=(p_sh, o_sh, b_sh),
                     out_shardings=(p_sh, o_sh, None),
                     donate_argnums=(0, 1))
    else:
        fn = jax.jit(train_step, donate_argnums=(0, 1))
    return StepBundle(fn, (p_struct, o_struct, b_struct), "train")


# ----------------------------------------------------------------- prefill
def build_prefill_step(cfg: ArchConfig, shape: InputShape, mesh=None,
                       backend: str = "xla",
                       max_seq: Optional[int] = None) -> StepBundle:
    shard = _sharder(mesh, cfg)
    max_seq = max_seq or shape.seq_len

    def prefill_step(params, batch):
        return lm.prefill(cfg, params, batch["tokens"],
                          max_seq=max_seq,
                          patches=batch.get("patches"),
                          enc_frames=batch.get("frames"),
                          backend=backend, shard=shard)

    p_struct = param_struct(cfg)
    b_struct = data_pipeline.batch_spec(cfg, shape)

    if mesh is not None:
        p_sh = param_shardings(p_struct, mesh)
        b_sh = batch_shardings(b_struct, mesh, cfg, profile_for(cfg))
        _, cache_struct = jax.eval_shape(prefill_step, p_struct, b_struct)
        c_sh = cache_shardings(cache_struct, mesh, cfg)
        fn = jax.jit(prefill_step, in_shardings=(p_sh, b_sh),
                     out_shardings=(None, c_sh))
    else:
        fn = jax.jit(prefill_step)
    return StepBundle(fn, (p_struct, b_struct), "prefill")


# ------------------------------------------------------------------ decode
def build_decode_step(cfg: ArchConfig, shape: InputShape, mesh=None,
                      backend: str = "xla") -> StepBundle:
    """serve_step: one new token for every sequence, KV cache of seq_len."""
    shard = _sharder(mesh, cfg)
    B = shape.global_batch

    def decode(params, token, caches, lengths):
        return lm.decode_step(cfg, params, token, caches, lengths,
                              backend=backend, shard=shard)

    p_struct = param_struct(cfg)
    # Cache structure comes from prefill's shape signature at max_seq=seq_len.
    prefill_shape = InputShape(shape.name, shape.seq_len, B, "prefill")
    b_struct = data_pipeline.batch_spec(cfg, prefill_shape)

    def _prefill(params, batch):
        return lm.prefill(cfg, params, batch["tokens"],
                          max_seq=shape.seq_len,
                          patches=batch.get("patches"),
                          enc_frames=batch.get("frames"))

    _, cache_struct = jax.eval_shape(_prefill, p_struct, b_struct)
    tok_struct = jax.ShapeDtypeStruct((B,), jnp.int32)
    len_struct = jax.ShapeDtypeStruct((B,), jnp.int32)

    if mesh is not None:
        p_sh = param_shardings(p_struct, mesh)
        c_sh = cache_shardings(cache_struct, mesh, cfg)
        baxes = batch_axes_of(mesh)
        bsize = 1
        for a in baxes:
            bsize *= mesh.shape[a]
        b_spec = P(baxes) if B % bsize == 0 else P()
        tok_sh = NamedSharding(mesh, b_spec)
        fn = jax.jit(decode,
                     in_shardings=(p_sh, tok_sh, c_sh, tok_sh),
                     out_shardings=(None, c_sh),
                     donate_argnums=(2,))
    else:
        fn = jax.jit(decode, donate_argnums=(2,))
    return StepBundle(fn, (p_struct, tok_struct, cache_struct, len_struct),
                      "decode")


def build_step(cfg: ArchConfig, shape: InputShape, mesh=None,
               backend: str = "xla", **kw) -> StepBundle:
    if shape.kind == "train":
        return build_train_step(cfg, shape, mesh, backend=backend, **kw)
    if shape.kind == "prefill":
        return build_prefill_step(cfg, shape, mesh, backend=backend, **kw)
    return build_decode_step(cfg, shape, mesh, backend=backend, **kw)


# ========================================================= per-layer probes
# XLA's cost analysis counts a while-loop (lax.scan) body ONCE, independent
# of trip count, so the main compile underreports flops/bytes/collectives by
# ~the layer count.  Each probe compiles ONE repeat of a stage's unit with
# no loop around it; the roofline then reconstructs
#   total = main + sum_s (repeats_s - 1) * probe_s.
def build_unit_probes(cfg: ArchConfig, shape: InputShape, mesh=None,
                      backend: str = "xla") -> Dict[str, Tuple[StepBundle, int]]:
    from repro.sharding.specs import unit_shardings, unit_struct

    shard = _sharder(mesh, cfg)
    plan = lm.build_plan(cfg)
    p_struct = param_struct(cfg)
    p_sh = param_shardings(p_struct, mesh) if mesh is not None else None
    probes: Dict[str, Tuple[StepBundle, int]] = {}

    B = shape.global_batch
    M = 1
    if shape.kind == "train" and mesh is not None:
        M = train_microbatches(cfg, shape, mesh)
        B = B // M          # probes see per-microbatch shapes
    S_tot = shape.seq_len + (cfg.n_patches or 0)
    D = cfg.d_model
    x_struct = jax.ShapeDtypeStruct((B, S_tot, D), jnp.bfloat16)
    xd_struct = jax.ShapeDtypeStruct((B, D), jnp.bfloat16)
    len_struct = jax.ShapeDtypeStruct((B,), jnp.int32)
    enc_struct = None
    if cfg.encoder is not None:
        enc_struct = jax.ShapeDtypeStruct(
            (B, cfg.encoder.n_frames, D), jnp.bfloat16)

    def x_sharding(struct=None):
        if mesh is None:
            return None
        from repro.sharding.specs import batch_shardings
        tree = {"x": struct if struct is not None else x_struct}
        return batch_shardings(tree, mesh, cfg, profile_for(cfg))["x"]

    for si, stage in enumerate(plan):
        key = f"stage{si}"
        u_struct = unit_struct(p_struct, key)
        u_sh = unit_shardings(p_sh, key) if mesh is not None else None

        has_cross = cfg.encoder is not None
        if shape.kind == "train":
            def probe(up, x, enc_out=None, stage=stage):
                def f(up, x):
                    y, _, aux = lm.apply_unit(
                        cfg, stage, up, x, enc_out=enc_out,
                        positions=jnp.arange(x.shape[1]), max_seq=None,
                        backend=backend, shard=shard)
                    return jnp.sum(y.astype(jnp.float32) ** 2) + aux
                f = jax.checkpoint(f)
                return jax.value_and_grad(f, argnums=(0, 1))(up, x)

            args = (u_struct, x_struct) + ((enc_struct,) if has_cross else ())
            if mesh is not None:
                in_sh = (u_sh, x_sharding()) + (
                    (x_sharding(enc_struct),) if has_cross else ())
        elif shape.kind == "prefill":
            def probe(up, x, enc_out=None, stage=stage):
                return lm.apply_unit(
                    cfg, stage, up, x, enc_out=enc_out,
                    positions=jnp.arange(x.shape[1]), max_seq=shape.seq_len,
                    backend=backend, shard=shard)

            args = (u_struct, x_struct) + ((enc_struct,) if has_cross else ())
            if mesh is not None:
                in_sh = (u_sh, x_sharding()) + (
                    (x_sharding(enc_struct),) if has_cross else ())
        else:
            # decode: cache slice from the decode bundle's cache struct
            bundle = build_decode_step(cfg, shape, mesh=None, backend=backend)
            cache_struct = bundle.arg_specs[2][key]
            c_struct = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                cache_struct)
            c_sh = None
            if mesh is not None:
                full_c_sh = cache_shardings(
                    {"c": bundle.arg_specs[2]}, mesh, cfg)["c"][key]
                from jax.sharding import NamedSharding as NS
                c_sh = jax.tree.map(
                    lambda ns: NS(ns.mesh, P(*ns.spec[1:])), full_c_sh)

            def probe(up, c, x, lengths, stage=stage):
                return lm.decode_unit(cfg, stage, up, c, x, lengths,
                                      backend=backend, shard=shard)

            args = (u_struct, c_struct, xd_struct, len_struct)
            if mesh is not None:
                baxes = batch_axes_of(mesh)
                bsz = 1
                for a in baxes:
                    bsz *= mesh.shape[a]
                tok_sh = NamedSharding(
                    mesh, P(baxes) if B % bsz == 0 else P())
                in_sh = (u_sh, c_sh, tok_sh, tok_sh)

        if mesh is not None:
            fn = jax.jit(probe, in_shardings=in_sh)
        else:
            fn = jax.jit(probe)
        probes[key] = (StepBundle(fn, args, f"probe-{shape.kind}"),
                       stage.repeats * M)

    # encoder probe (whisper): forward-only layer over the frame sequence
    if cfg.encoder is not None and shape.kind in ("train", "prefill"):
        enc_u_struct = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
            p_struct["encoder"]["layers"])

        def enc_probe(up, x):
            if shape.kind == "train":
                def f(up, x):
                    y = lm.encoder_unit(cfg, up, x, backend=backend,
                                        shard=shard)
                    return jnp.sum(y.astype(jnp.float32) ** 2)
                return jax.value_and_grad(jax.checkpoint(f),
                                          argnums=(0, 1))(up, x)
            return lm.encoder_unit(cfg, up, x, backend=backend, shard=shard)

        if mesh is not None:
            # reuse param rules on the encoder subtree, then strip stack axis
            full = param_shardings(p_struct, mesh)["encoder"]["layers"]
            enc_u_sh = jax.tree.map(
                lambda ns: NamedSharding(ns.mesh, P(*ns.spec[1:])), full)
            fn = jax.jit(enc_probe,
                         in_shardings=(enc_u_sh, x_sharding(enc_struct)))
        else:
            fn = jax.jit(enc_probe)
        probes["encoder"] = (
            StepBundle(fn, (enc_u_struct, enc_struct), f"probe-{shape.kind}"),
            cfg.encoder.n_layers * M)
    return probes
