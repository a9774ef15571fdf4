"""The program's configuration for a decoder with latent attention (MLA)
and a mixture of experts, DeepSeek-V2's structure.

Maps the published keys of a configuration file (``configs/*.json``, the
keys of DeepSeek-V2's ``config.json``) onto the program's ``ArchConfig``
for its ``arch``, and checks that the result is the structure this step
kind's counts and reference describe: latent attention with full-rank
queries in every layer, ``first_k_dense_replace`` leading layers with a
dense SwiGLU MLP of ``intermediate_size``, then in every layer softmax
routing over ``n_routed_experts`` SwiGLU experts of
``moe_intermediate_size`` (greedy top-k, no group limit) beside
``n_shared_experts`` shared ones, RMS norms, an untied head. Absent keys
take the defaults of DeepSeek-V2's configuration class: no rotary scaling,
gates not renormalised, a routed scale of 1. The file's ``rope_scaling``
(YaRN, or none) is the program's: the result is a ``ScaledRopeArchConfig``
that holds it.
"""

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
#: Keys of ``rope_scaling`` (YaRN) and the ``RopeScaling`` fields.
YARN_FIELDS = {
    "factor": "factor",
    "original_max_position_embeddings": "original_max_position",
    "beta_fast": "beta_fast",
    "beta_slow": "beta_slow",
    "mscale": "mscale",
    "mscale_all_dim": "mscale_all_dim",
}
#: Published keys whose other values name a structure this step lacks.
STRUCTURE = {
    "hidden_act": "silu",
    "scoring_func": "softmax",
    "topk_method": "greedy",
    "n_group": 1,
    "topk_group": 1,
    "moe_layer_freq": 1,
    "attention_bias": False,
    "tie_word_embeddings": False,
    "q_lora_rank": None,
}


def _pick(cfg: Dict, fields: Dict[str, str]) -> Dict:
    return {f: cfg[k] for k, f in fields.items() if k in cfg}


def _refuse(cfg: Dict, what: str) -> None:
    raise ValueError(f"{cfg['arch']}: {what} is a structure that the "
                     f"{cfg['step_kind']} step does not describe")


def rope_scaling(cfg: Dict):
    """The ``RopeScaling`` that the file's ``rope_scaling`` states, None
    where it states none; any kind but YaRN is refused."""
    from repro.configs import RopeScaling
    rs = cfg.get("rope_scaling")
    if rs is None:
        return None
    if rs.get("type") != "yarn":
        _refuse(cfg, f"rope_scaling type {rs.get('type')!r}")
    return RopeScaling(**{f: float(rs[k]) if f != "original_max_position"
                          else int(rs[k])
                          for k, f in YARN_FIELDS.items() if k in rs})


def program_config(cfg: Dict):
    """The program's configuration for a configuration file: the widths,
    depth and routing of the file over the program's entry for ``arch``."""
    from repro.configs import ArchConfig, ScaledRopeArchConfig, get_arch
    base = get_arch(cfg["arch"])
    if base.mla is None or base.moe is None:
        raise ValueError(f"{cfg['arch']}: the {cfg['step_kind']} step needs "
                         f"latent attention and experts")
    for k, v in STRUCTURE.items():
        if k in cfg and cfg[k] != v:
            _refuse(cfg, f"{k}={cfg[k]!r}")
    fields = {f.name: getattr(base, f.name)
              for f in dataclasses.fields(ArchConfig)}
    fields.update(
        mla=dataclasses.replace(base.mla, **_pick(cfg, MLA_FIELDS)),
        moe=dataclasses.replace(
            base.moe, norm_topk_prob=bool(cfg.get("norm_topk_prob", False)),
            routed_scaling_factor=float(cfg.get("routed_scaling_factor",
                                                1.0)),
            **_pick(cfg, MOE_FIELDS)),
        **_pick(cfg, CONFIG_FIELDS))
    pcfg = ScaledRopeArchConfig(**fields, rope_scaling=rope_scaling(cfg))
    want = dict(family="moe", attn_kind="mla", act="swiglu", norm="rms",
                tie_embeddings=False, ssm=None, rglru=None, encoder=None)
    for k, v in want.items():
        if getattr(pcfg, k) != v:
            raise ValueError(f"{cfg['arch']}: {k}={getattr(pcfg, k)!r}, the "
                             f"{cfg['step_kind']} step needs {v!r}")
    return pcfg
