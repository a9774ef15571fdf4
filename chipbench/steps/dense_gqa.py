"""The program's configuration for a dense GQA decoder.

Maps the published keys of a configuration file (``configs/*.json``) onto
the program's ``ArchConfig`` for its ``arch``, and checks that the result
is the structure this step kind's counts and reference describe: one
dense SwiGLU MLP and grouped-query attention in every layer, RMS norms,
an untied head.
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
    "intermediate_size": "d_ff",
    "head_dim": "head_dim",
    "rope_theta": "rope_theta",
}


def program_config(cfg: Dict):
    """The program's configuration for a configuration file: the widths and
    depth of the file over the program's entry for ``arch``."""
    from repro.configs import get_arch
    base = get_arch(cfg["arch"])
    changes = {f: cfg[k] for k, f in CONFIG_FIELDS.items() if k in cfg}
    pcfg = dataclasses.replace(base, **changes)
    want = dict(family="dense", attn_kind="gqa", act="swiglu", norm="rms",
                tie_embeddings=False, moe=None, ssm=None, rglru=None,
                encoder=None)
    for k, v in want.items():
        if getattr(pcfg, k) != v:
            raise ValueError(f"{cfg['arch']}: {k}={getattr(pcfg, k)!r}, the "
                             f"{cfg['step_kind']} step needs {v!r}")
    return pcfg
