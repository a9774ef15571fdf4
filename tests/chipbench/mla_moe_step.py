"""A step module for a decoder with latent attention (MLA) and a mixture of
experts, as a later step kind adds it under ``chipbench/steps/``: it maps
DeepSeek-V2's published keys onto the program's ``ArchConfig``. The tests
copy it into a copy of the benchmark; the benchmark itself has no such
step kind yet."""

from __future__ import annotations

import dataclasses
from typing import Dict

#: Keys of a configuration file and the program's ``ArchConfig`` fields.
CONFIG_FIELDS = {
    "vocab_size": "vocab_size",
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "moe_intermediate_size": "d_ff",
    "rope_theta": "rope_theta",
}
#: Keys of the latent attention and its ``MLAConfig`` fields.
MLA_FIELDS = {
    "kv_lora_rank": "kv_lora_rank",
    "q_lora_rank": "q_lora_rank",
    "qk_nope_head_dim": "qk_nope_dim",
    "qk_rope_head_dim": "qk_rope_dim",
    "v_head_dim": "v_head_dim",
}
#: Keys of the experts and their ``MoEConfig`` fields; the dense layers'
#: width is ``intermediate_size``, the experts' ``moe_intermediate_size``.
MOE_FIELDS = {
    "n_routed_experts": "n_experts",
    "num_experts_per_tok": "top_k",
    "n_shared_experts": "n_shared",
    "first_k_dense_replace": "first_dense_layers",
    "intermediate_size": "d_ff_dense",
}


def _pick(cfg: Dict, fields: Dict[str, str]) -> Dict:
    return {f: cfg[k] for k, f in fields.items() if k in cfg}


def program_config(cfg: Dict):
    from repro.configs import get_arch
    base = get_arch(cfg["arch"])
    if base.mla is None or base.moe is None:
        raise ValueError(f"{cfg['arch']}: the {cfg['step_kind']} step needs "
                         f"latent attention and experts")
    pcfg = dataclasses.replace(
        base, mla=dataclasses.replace(base.mla, **_pick(cfg, MLA_FIELDS)),
        moe=dataclasses.replace(base.moe, **_pick(cfg, MOE_FIELDS)),
        **_pick(cfg, CONFIG_FIELDS))
    want = dict(family="moe", attn_kind="mla", act="swiglu", norm="rms",
                tie_embeddings=False, ssm=None, rglru=None, encoder=None)
    for k, v in want.items():
        if getattr(pcfg, k) != v:
            raise ValueError(f"{cfg['arch']}: {k}={getattr(pcfg, k)!r}, the "
                             f"{cfg['step_kind']} step needs {v!r}")
    return pcfg
