"""The composable language model: plan construction, init, forward, loss,
prefill and decode for every architecture in the zoo.

A model is a sequence of *stages*; each stage scans a fixed *unit* (tuple of
LayerSpecs) over ``repeats`` stacked parameter sets, keeping the lowered HLO
compact regardless of depth.  Mixers: GQA / sliding-window GQA / MLA /
Mamba-2 SSD / RG-LRU.  FFNs: dense (SwiGLU/GeGLU/GELU) or MoE.  Optional
encoder (whisper) and patch-embedding stub (pixtral).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.sharding.annotate import NULL_SHARDER

from . import attention as attn
from . import mla as mla_mod
from . import moe as moe_mod
from . import rglru as rglru_mod
from . import ssm as ssm_mod
from .layers import (
    DEFAULT_COMPUTE_DTYPE,
    apply_mlp,
    apply_norm,
    embed,
    embedding_init,
    mlp_init,
    norm_init,
    unembed,
)


@dataclass(frozen=True)
class LayerSpec:
    mixer: str                  # gqa|local|mla|ssd|rglru
    ffn: str                    # dense|moe|none
    cross: bool = False
    d_ff: Optional[int] = None  # per-layer FFN width override


@dataclass(frozen=True)
class Stage:
    unit: Tuple[LayerSpec, ...]
    repeats: int


def build_plan(cfg: ArchConfig) -> Tuple[Stage, ...]:
    if cfg.ssm is not None:
        return (Stage((LayerSpec("ssd", "none"),), cfg.n_layers),)
    if cfg.rglru is not None:
        pat = tuple("rglru" if p == "rec" else "local" for p in cfg.rglru.pattern)
        unit = tuple(LayerSpec(m, "dense") for m in pat)
        full, rem = divmod(cfg.n_layers, len(pat))
        stages = [Stage(unit, full)] if full else []
        if rem:
            stages.append(Stage(unit[:rem], 1))
        return tuple(stages)
    mixer = "mla" if cfg.attn_kind == "mla" else "gqa"
    if cfg.moe is not None:
        stages = []
        nd = cfg.moe.first_dense_layers
        if nd:
            stages.append(Stage(
                (LayerSpec(mixer, "dense", d_ff=cfg.moe.d_ff_dense),), nd))
        stages.append(Stage((LayerSpec(mixer, "moe"),), cfg.n_layers - nd))
        return tuple(stages)
    return (Stage((LayerSpec(mixer, "dense", cross=cfg.encoder is not None),),
                  cfg.n_layers),)


# ================================================================== init
def _layer_init(key, cfg: ArchConfig, spec: LayerSpec) -> Dict:
    ks = jax.random.split(key, 6)
    p: Dict = {"norm1": norm_init(cfg.d_model, cfg.norm)}
    if spec.mixer in ("gqa", "local"):
        p["mixer"] = attn.gqa_init(ks[0], cfg.d_model, cfg.n_heads,
                                   cfg.n_kv_heads, cfg.head_dim_,
                                   bias=cfg.norm == "layer")
    elif spec.mixer == "mla":
        p["mixer"] = mla_mod.mla_init(ks[0], cfg.d_model, cfg.n_heads, cfg.mla)
    elif spec.mixer == "ssd":
        p["mixer"] = ssm_mod.mamba2_init(ks[0], cfg.d_model, cfg.ssm)
    elif spec.mixer == "rglru":
        p["mixer"] = rglru_mod.rglru_block_init(ks[0], cfg.d_model, cfg.rglru)
    else:
        raise ValueError(spec.mixer)
    if spec.cross:
        p["norm_cross"] = norm_init(cfg.d_model, cfg.norm)
        p["cross"] = attn.cross_init(ks[1], cfg.d_model, cfg.n_heads,
                                     cfg.n_kv_heads, cfg.head_dim_)
    if spec.ffn == "dense":
        p["norm2"] = norm_init(cfg.d_model, cfg.norm)
        p["ffn"] = mlp_init(ks[2], cfg.d_model, spec.d_ff or cfg.d_ff, cfg.act)
    elif spec.ffn == "moe":
        p["norm2"] = norm_init(cfg.d_model, cfg.norm)
        p["ffn"] = moe_mod.moe_init(ks[2], cfg.d_model, cfg.d_ff, cfg.moe)
    return p


def _encoder_layer_init(key, cfg: ArchConfig) -> Dict:
    ks = jax.random.split(key, 3)
    return {
        "norm1": norm_init(cfg.d_model, cfg.norm),
        "mixer": attn.gqa_init(ks[0], cfg.d_model, cfg.n_heads,
                               cfg.n_kv_heads, cfg.head_dim_, bias=True),
        "norm2": norm_init(cfg.d_model, cfg.norm),
        "ffn": mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.act),
    }


def init(cfg: ArchConfig, key) -> Dict:
    plan = build_plan(cfg)
    keys = jax.random.split(key, len(plan) + 4)
    params: Dict = {
        "embed": embedding_init(keys[0], cfg.padded_vocab, cfg.d_model),
        "final_norm": norm_init(cfg.d_model, cfg.norm),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "w": jax.random.normal(
                keys[1], (cfg.d_model, cfg.padded_vocab)) * 0.02}
    if cfg.encoder is not None:
        enc_keys = jax.random.split(keys[2], 2)
        params["encoder"] = {
            "layers": jax.vmap(lambda k: _encoder_layer_init(k, cfg))(
                jax.random.split(enc_keys[0], cfg.encoder.n_layers)),
            "final_norm": norm_init(cfg.d_model, cfg.norm),
        }
    for si, stage in enumerate(plan):
        stage_p = {}
        for ui, spec in enumerate(stage.unit):
            lk = jax.random.split(jax.random.fold_in(keys[3 + si], ui),
                                  stage.repeats)
            stage_p[f"u{ui}"] = jax.vmap(
                lambda k, s=spec: _layer_init(k, cfg, s))(lk)
        params[f"stage{si}"] = stage_p
    return params


# ================================================================ forward
def _sinusoid(n: int, d: int) -> jnp.ndarray:
    pos = jnp.arange(n)[:, None].astype(jnp.float32)
    dim = jnp.arange(d // 2)[None, :].astype(jnp.float32)
    angle = pos / jnp.power(10_000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)


def _apply_layer(cfg: ArchConfig, spec: LayerSpec, p: Dict, x, *,
                 enc_out=None, positions=None, max_seq=None,
                 backend="xla", shard=NULL_SHARDER, dtype=DEFAULT_COMPUTE_DTYPE):
    """One layer, full sequence.  Returns (x, cache, aux)."""
    h = apply_norm(p["norm1"], x, cfg.norm)
    cache = {}
    rope = cfg.rope_theta if cfg.attn_kind != "none" or cfg.rglru else None
    if spec.mixer in ("gqa", "local"):
        window = cfg.rglru.window if (spec.mixer == "local" and cfg.rglru) else 0
        mix, kv = attn.gqa_apply(
            p["mixer"], h, rope_theta=rope,
            mask_kind="window" if spec.mixer == "local" else "causal",
            window=window, positions=positions, backend=backend,
            shard=shard, dtype=dtype)
        cache = _ring_or_pad_kv(kv, spec, cfg, max_seq)
    elif spec.mixer == "mla":
        mix, kv = mla_mod.mla_apply(
            p["mixer"], h, cfg.mla, rope_theta=cfg.rope_theta,
            rope_scaling=cfg.rope_scaling, positions=positions,
            backend=backend, shard=shard, dtype=dtype)
        cache = _pad_mla(kv, max_seq)
    elif spec.mixer == "ssd":
        mix, cache = ssm_mod.mamba2_apply(
            p["mixer"], h, cfg.ssm, cfg.d_model, backend=backend,
            shard=shard, dtype=dtype)
    elif spec.mixer == "rglru":
        mix, cache = rglru_mod.rglru_block_apply(
            p["mixer"], h, cfg.rglru, backend=backend, shard=shard,
            dtype=dtype)
    x = x + mix
    if spec.cross and enc_out is not None:
        hc = apply_norm(p["norm_cross"], x, cfg.norm)
        ckv = attn.cross_kv(p["cross"], enc_out, dtype)
        x = x + attn.cross_apply(p["cross"], hc, ckv, backend=backend,
                                 dtype=dtype)
        cache["cross"] = ckv
    aux = jnp.zeros((), jnp.float32)

    def whook(w):
        return shard.weight_for_batch(w, x.shape[0])

    if spec.ffn == "dense":
        h2 = apply_norm(p["norm2"], x, cfg.norm)
        # nested remat: the FFN's [*, d_ff] intermediates are the largest
        # per-layer activations; recompute them inside the layer's backward
        ffn_fn = jax.checkpoint(
            lambda q, v: apply_mlp(q, v, cfg.act, dtype, whook=whook))
        x = x + ffn_fn(p["ffn"], h2)
    elif spec.ffn == "moe":
        h2 = apply_norm(p["norm2"], x, cfg.norm)
        y, aux = moe_mod.moe_apply(p["ffn"], h2, cfg.moe, shard=shard,
                                   dtype=dtype)
        x = x + y
    x = shard.activations(x)
    return x, cache, aux


def _ring_or_pad_kv(kv: Dict, spec: LayerSpec, cfg: ArchConfig,
                    max_seq: Optional[int]) -> Dict:
    if max_seq is None:
        return {}
    k, v = kv["k"], kv["v"]
    S = k.shape[1]
    if spec.mixer == "local" and cfg.rglru:
        W = cfg.rglru.window
        n = min(S, W)
        slots = (jnp.arange(S - n, S) % W)
        def ring(a):
            return jnp.zeros((a.shape[0], W) + a.shape[2:], a.dtype
                             ).at[:, slots].set(a[:, -n:])
        return {"k": ring(k), "v": ring(v)}
    pad = max_seq - S
    if pad > 0:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return {"k": k, "v": v}


def _pad_mla(kv: Dict, max_seq: Optional[int]) -> Dict:
    if max_seq is None:
        return {}
    pad = max_seq - kv["c_kv"].shape[1]
    if pad > 0:
        return {"c_kv": jnp.pad(kv["c_kv"], ((0, 0), (0, pad), (0, 0))),
                "k_rope": jnp.pad(kv["k_rope"], ((0, 0), (0, pad), (0, 0)))}
    return {"c_kv": kv["c_kv"], "k_rope": kv["k_rope"]}


#: The stacked weights of a stage's routed experts.
_EXPERT_LEAVES = ("gate_w", "up_w", "down_w")


def _hold_experts(stage: Stage, stage_p: Dict, shard, hold: bool
                  ) -> Tuple[Dict, Optional[Dict]]:
    """In a served step on one device (``hold``, no mesh), take a stage's
    stacked expert weights ``[repeats, E, ...]`` out of what its layer scan
    slices: the grouped products read a layer's experts from the whole
    stack (``ops.moe_apply``), where a slice of the stack would be copied
    for the kernel in every layer and step.  Where gradients are taken the
    scan slices them, as every other weight, so that no layer's gradient
    spans the whole stack.  Returns (the rest of the stage's parameters,
    the held weights by unit, or None where nothing is held)."""
    moe_units = [f"u{ui}" for ui, spec in enumerate(stage.unit)
                 if spec.ffn == "moe"]
    if not (hold and moe_units) or getattr(shard, "mesh", None) is not None:
        return stage_p, None
    rest, held = dict(stage_p), {}
    for u in moe_units:
        ffn = dict(rest[u]["ffn"])
        held[u] = {k: ffn.pop(k) for k in _EXPERT_LEAVES}
        rest[u] = {**rest[u], "ffn": ffn}
    return rest, held


def _with_held(repeat_p: Dict, held: Dict, layer) -> Dict:
    """One repeat's parameters with its units' held expert stacks and the
    repeat's index into them (``ffn["layer"]``)."""
    out = dict(repeat_p)
    for u, w in held.items():
        out[u] = {**out[u], "ffn": {**out[u]["ffn"], **w, "layer": layer}}
    return out


def encoder_unit(cfg: ArchConfig, p: Dict, x, *, backend="xla",
                 shard=NULL_SHARDER, dtype=DEFAULT_COMPUTE_DTYPE):
    """One encoder layer (the encoder scan body)."""
    h = apply_norm(p["norm1"], x, cfg.norm)
    mix, _ = attn.gqa_apply(p["mixer"], h, rope_theta=None,
                            mask_kind="none", backend=backend,
                            shard=shard, dtype=dtype)
    x = x + mix
    h2 = apply_norm(p["norm2"], x, cfg.norm)
    x = x + apply_mlp(p["ffn"], h2, cfg.act, dtype)
    return shard.activations(x)


def _encode(cfg: ArchConfig, params: Dict, frames: jnp.ndarray, *,
            backend="xla", shard=NULL_SHARDER,
            dtype=DEFAULT_COMPUTE_DTYPE) -> jnp.ndarray:
    """Whisper-style encoder over precomputed frame embeddings [B, F, D]."""
    x = frames.astype(dtype) + _sinusoid(frames.shape[1],
                                         cfg.d_model).astype(dtype)
    x = shard.activations(x)

    def body(x, p):
        return encoder_unit(cfg, p, x, backend=backend, shard=shard,
                            dtype=dtype), None

    x, _ = jax.lax.scan(body, x, params["encoder"]["layers"])
    return apply_norm(params["encoder"]["final_norm"], x, cfg.norm)


def apply_unit(cfg: ArchConfig, stage: Stage, repeat_p: Dict, x, *,
               enc_out=None, positions=None, max_seq=None, backend="xla",
               shard=NULL_SHARDER, dtype=DEFAULT_COMPUTE_DTYPE):
    """One repeat of a stage's unit (the scan body).  Returns
    (x, cache entries, aux)."""
    entries = {}
    aux = jnp.zeros((), jnp.float32)
    for ui, spec in enumerate(stage.unit):
        x, cache, a = _apply_layer(
            cfg, spec, repeat_p[f"u{ui}"], x, enc_out=enc_out,
            positions=positions, max_seq=max_seq, backend=backend,
            shard=shard, dtype=dtype)
        entries[f"u{ui}"] = cache
        aux = aux + a
    return x, entries, aux


def forward(
    cfg: ArchConfig,
    params: Dict,
    tokens: jnp.ndarray,                 # [B, S_text]
    *,
    patches: Optional[jnp.ndarray] = None,      # [B, P, D] VLM stub embeds
    enc_frames: Optional[jnp.ndarray] = None,   # [B, F, D] audio stub embeds
    collect_cache: bool = False,
    max_seq: Optional[int] = None,
    backend: str = "xla",
    shard=NULL_SHARDER,
    remat: bool = False,
    return_hidden: bool = False,
    hold_experts: bool = False,
    dtype=DEFAULT_COMPUTE_DTYPE,
) -> Tuple:
    """Returns (logits [B,S,V] — or final hidden states if
    ``return_hidden``, for the vocab-chunked loss — , aux, caches|None).
    ``hold_experts`` (served prefill, no gradient): the expert layers read
    their weights in place from the stage's stack (``_hold_experts``)."""
    plan = build_plan(cfg)
    x = embed(params["embed"], tokens, dtype)
    if patches is not None:
        x = jnp.concatenate([patches.astype(dtype), x], axis=1)
    S = x.shape[1]
    positions = jnp.arange(S)
    x = shard.activations(x)

    enc_out = None
    if cfg.encoder is not None and enc_frames is not None:
        enc_out = _encode(cfg, params, enc_frames, backend=backend,
                          shard=shard, dtype=dtype)

    cache_seq = max_seq if collect_cache else None
    caches: Dict = {}
    aux_total = jnp.zeros((), jnp.float32)
    for si, stage in enumerate(plan):
        stage_p, held = _hold_experts(stage, params[f"stage{si}"], shard,
                                      hold_experts)

        def body(carry, xs, stage=stage, held=held):
            x, aux = carry
            repeat_p = (xs if held is None
                        else _with_held(xs[0], held, xs[1]))
            x, entries, a = apply_unit(
                cfg, stage, repeat_p, x, enc_out=enc_out,
                positions=positions, max_seq=cache_seq, backend=backend,
                shard=shard, dtype=dtype)
            return (x, aux + a), (entries if collect_cache else None)

        body_fn = jax.checkpoint(body) if remat else body
        xs = (stage_p if held is None
              else (stage_p, jnp.arange(stage.repeats)))
        (x, aux_total), ys = jax.lax.scan(body_fn, (x, aux_total), xs)
        if collect_cache:
            caches[f"stage{si}"] = ys

    x = apply_norm(params["final_norm"], x, cfg.norm)
    if return_hidden:
        return x, aux_total, (caches if collect_cache else None)
    if cfg.tie_embeddings:
        logits = unembed(params["embed"], x, dtype)
    else:
        logits = x @ params["lm_head"]["w"].astype(dtype)
    logits = shard.logits(logits)
    return logits, aux_total, (caches if collect_cache else None)


# =================================================================== loss
def _chunked_nll(x: jnp.ndarray, table: jnp.ndarray, transpose: bool,
                 targets: jnp.ndarray, vocab: int,
                 chunk: int = 8192, dtype=DEFAULT_COMPUTE_DTYPE):
    """Online-logsumexp cross entropy over vocabulary chunks.

    Materializing fp32 logits [B, S, V] costs gigabytes per device at the
    assigned vocab sizes (up to 256k); streaming the head matmul over vocab
    chunks with a checkpointed scan bounds the transient to [B, S, chunk]
    (EXPERIMENTS.md §Perf).  ``table`` is [V, D] if ``transpose`` (tied
    embeddings) else [D, V].  Returns (nll [B,S], lse [B,S]).
    """
    B, S, D = x.shape
    V = table.shape[0] if transpose else table.shape[1]
    chunk = min(chunk, V)
    n_chunks = -(-V // chunk)

    def body(carry, i):
        m, se, tl = carry
        start = i * chunk
        if transpose:
            wc = jax.lax.dynamic_slice_in_dim(table, start, chunk, 0)
            logits = (x @ wc.astype(dtype).T).astype(jnp.float32)
        else:
            wc = jax.lax.dynamic_slice_in_dim(table, start, chunk, 1)
            logits = (x @ wc.astype(dtype)).astype(jnp.float32)
        cols = start + jnp.arange(chunk)
        logits = jnp.where(cols[None, None, :] < vocab, logits, -1e30)
        new_m = jnp.maximum(m, logits.max(-1))
        se = se * jnp.exp(m - new_m) + jnp.exp(
            logits - new_m[..., None]).sum(-1)
        local = targets - start
        in_range = (local >= 0) & (local < chunk)
        lt = jnp.take_along_axis(
            logits, jnp.clip(local, 0, chunk - 1)[..., None], axis=-1)[..., 0]
        tl = jnp.where(in_range, lt, tl)
        return (new_m, se, tl), None

    init = (jnp.full((B, S), -1e30, jnp.float32),
            jnp.zeros((B, S), jnp.float32),
            jnp.zeros((B, S), jnp.float32))
    (m, se, tl), _ = jax.lax.scan(jax.checkpoint(body), init,
                                  jnp.arange(n_chunks))
    lse = jnp.log(jnp.maximum(se, 1e-30)) + m
    return lse - tl, lse


def loss_fn(cfg: ArchConfig, params: Dict, batch: Dict, *,
            backend: str = "xla", shard=NULL_SHARDER, remat: bool = False,
            aux_coef: float = 0.01, z_coef: float = 1e-4,
            dtype=DEFAULT_COMPUTE_DTYPE) -> Tuple[jnp.ndarray, Dict]:
    """Next-token cross entropy (+ MoE aux + z-loss), vocab-chunked."""
    hidden, aux, _ = forward(
        cfg, params, batch["tokens"], patches=batch.get("patches"),
        enc_frames=batch.get("frames"), backend=backend, shard=shard,
        remat=remat, dtype=dtype, return_hidden=True)
    n_prefix = 0 if batch.get("patches") is None else batch["patches"].shape[1]
    x = hidden[:, n_prefix:-1, :]
    targets = batch["tokens"][:, 1:]
    if cfg.tie_embeddings:
        nll, lse = _chunked_nll(x, params["embed"]["table"], True, targets,
                                cfg.padded_vocab, dtype=dtype)
    else:
        nll, lse = _chunked_nll(x, params["lm_head"]["w"], False, targets,
                                cfg.padded_vocab, dtype=dtype)
    nll = nll.mean()
    z_loss = z_coef * jnp.square(lse).mean()
    total = nll + z_loss + aux_coef * aux
    return total, {"nll": nll, "aux": aux, "z": z_loss}


# ================================================================ serving
def prefill(cfg: ArchConfig, params: Dict, tokens, *, max_seq: int,
            patches=None, enc_frames=None, backend="xla",
            shard=NULL_SHARDER, dtype=DEFAULT_COMPUTE_DTYPE):
    """Run the prompt, return (last-token logits [B,V], caches)."""
    total = tokens.shape[1] + (patches.shape[1] if patches is not None else 0)
    if max_seq < total:
        raise ValueError(
            f"max_seq={max_seq} smaller than prompt length {total} "
            "(includes patch prefix)")
    # head applied to the LAST position only: computing (and sharding-
    # constraining) full [B, S, V] logits forced XLA to materialize tens of
    # GiB at 32k x 256k vocab (EXPERIMENTS.md §Perf)
    hidden, _, caches = forward(
        cfg, params, tokens, patches=patches, enc_frames=enc_frames,
        collect_cache=True, max_seq=max_seq, backend=backend, shard=shard,
        return_hidden=True, hold_experts=True, dtype=dtype)
    last = hidden[:, -1, :]
    if cfg.tie_embeddings:
        logits = last @ params["embed"]["table"].astype(dtype).T
    else:
        logits = last @ params["lm_head"]["w"].astype(dtype)
    return logits, caches


def decode_unit(cfg: ArchConfig, stage: Stage, repeat_p: Dict,
                repeat_c: Dict, x, lengths, *, backend="xla",
                shard=NULL_SHARDER, dtype=DEFAULT_COMPUTE_DTYPE):
    """One repeat of a stage's unit in decode mode (the decode scan body).
    Returns (x, updated cache entries)."""
    new_entries = {}
    for ui, spec in enumerate(stage.unit):
        p, c = repeat_p[f"u{ui}"], repeat_c[f"u{ui}"]
        h = apply_norm(p["norm1"], x[:, None, :], cfg.norm)[:, 0]
        if spec.mixer in ("gqa", "local"):
            window = (cfg.rglru.window
                      if (spec.mixer == "local" and cfg.rglru) else 0)
            mix, nc = attn.gqa_decode(
                p["mixer"], h, {"k": c["k"], "v": c["v"]}, lengths,
                rope_theta=cfg.rope_theta, window=window, backend=backend,
                dtype=dtype)
        elif spec.mixer == "mla":
            mix, nc = mla_mod.mla_decode(
                p["mixer"], h, {"c_kv": c["c_kv"], "k_rope": c["k_rope"]},
                lengths, cfg.mla, rope_theta=cfg.rope_theta,
                rope_scaling=cfg.rope_scaling, dtype=dtype)
        elif spec.mixer == "ssd":
            mix, nc = ssm_mod.mamba2_decode(
                p["mixer"], h, c, cfg.ssm, cfg.d_model, dtype=dtype)
        else:
            mix, nc = rglru_mod.rglru_block_decode(
                p["mixer"], h, c, cfg.rglru, dtype=dtype)
        x = x + mix
        if spec.cross and "cross" in c:
            hc = apply_norm(p["norm_cross"], x[:, None, :], cfg.norm)
            xc = attn.cross_apply(p["cross"], hc, c["cross"],
                                  backend=backend, dtype=dtype)
            x = x + xc[:, 0]
            nc["cross"] = c["cross"]
        if spec.ffn in ("dense", "moe"):
            h2 = apply_norm(p["norm2"], x[:, None, :], cfg.norm)
            if spec.ffn == "dense":
                x = x + apply_mlp(p["ffn"], h2, cfg.act, dtype)[:, 0]
            else:
                y, _ = moe_mod.moe_apply(p["ffn"], h2, cfg.moe, shard=shard,
                                         dtype=dtype)
                x = x + y[:, 0]
        new_entries[f"u{ui}"] = nc
    return x, new_entries


def decode_step(
    cfg: ArchConfig,
    params: Dict,
    token: jnp.ndarray,                  # [B] current token ids
    caches: Dict,
    lengths: jnp.ndarray,                # [B] positions already cached
    *,
    backend: str = "xla",
    shard=NULL_SHARDER,
    dtype=DEFAULT_COMPUTE_DTYPE,
) -> Tuple[jnp.ndarray, Dict]:
    """One token for every sequence in the batch: (logits [B,V], caches)."""
    plan = build_plan(cfg)
    x = embed(params["embed"], token, dtype)                  # [B,D]
    x = shard.decode_activations(x)
    new_caches: Dict = {}
    for si, stage in enumerate(plan):
        stage_p, held = _hold_experts(stage, params[f"stage{si}"], shard,
                                      True)
        stage_c = caches[f"stage{si}"]

        def body(x, inp, stage=stage, held=held):
            repeat_p, repeat_c = inp[:2]
            if held is not None:
                repeat_p = _with_held(repeat_p, held, inp[2])
            return decode_unit(cfg, stage, repeat_p, repeat_c, x, lengths,
                               backend=backend, shard=shard, dtype=dtype)

        xs = ((stage_p, stage_c) if held is None
              else (stage_p, stage_c, jnp.arange(stage.repeats)))
        x, new_stage_c = jax.lax.scan(body, x, xs)
        new_caches[f"stage{si}"] = new_stage_c

    x = apply_norm(params["final_norm"], x, cfg.norm)
    if cfg.tie_embeddings:
        logits = unembed(params["embed"], x, dtype)
    else:
        logits = x @ params["lm_head"]["w"].astype(dtype)
    return logits, new_caches
