"""Operations and bytes of one served step of a decoder with latent
attention and a mixture of experts (DeepSeek-V2's structure, the step
kind ``deepseek_v2``), and of its expert layers and latent attention
alone.

Counted from the configuration's shapes (the keys of ``configs/*.json``),
as the algorithm needs them at the compute dtype, bfloat16, whatever the
program stores or reads today:

- every weight that a step uses is read once at 2 bytes a parameter; a
  decode step reads only the routed experts that the batch's tokens
  touch, taken as ``E * (1 - (1 - k/E) ** tokens)`` of the ``E`` experts
  under uniform routing of ``k`` experts a token (an expectation: a step
  with uneven routing touches fewer); a prefill of thousands of tokens
  touches every expert;
- the latent cache holds ``kv_lora_rank + qk_rope_head_dim`` values a
  position and layer, read over the positions filled and written for the
  new ones, at 2 bytes a value;
- decode attends in the absorbed form (the query taken into the latent
  space through ``W_uk``, the context out of it through ``W_uv``), the
  prefill in the expanded form; both multiply each projection weight once
  a token;
- a matrix product of an m x k by a k x n matrix is 2mkn operations; each
  routed (token, expert) pair is one SwiGLU of the expert's width.

Norms, rotary embeddings, softmax, the router's top-k, the sort and
combine of the assignments and the sampling argmax are left out (no
weight bytes beyond the norm scales, counted at 2 bytes like the rest).
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

BF16 = 2


class Dims(NamedTuple):
    d: int
    heads: int
    nope: int
    rope: int
    v: int
    latent: int
    ff_dense: int
    ff: int
    experts: int
    top_k: int
    shared: int
    dense_layers: int
    layers: int
    vocab: int


def dims(cfg: Dict) -> Dims:
    return Dims(cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                cfg["v_head_dim"], cfg["kv_lora_rank"],
                cfg["intermediate_size"], cfg["moe_intermediate_size"],
                cfg["n_routed_experts"], cfg["num_experts_per_tok"],
                cfg["n_shared_experts"], cfg["first_k_dense_replace"],
                cfg["num_hidden_layers"], cfg["vocab_size"])


def attention_params(cfg: Dict) -> int:
    """Parameters of one layer's latent attention: the query projection,
    the latent down-projection with its norm, the rotary key projection,
    the key and value up-projections and the output projection."""
    m = dims(cfg)
    return (m.d * m.heads * (m.nope + m.rope) + m.d * m.latent + m.latent
            + m.d * m.rope + m.latent * m.heads * (m.nope + m.v)
            + m.heads * m.v * m.d)


def expert_params(cfg: Dict) -> int:
    """Parameters of one routed expert (a SwiGLU of the expert width)."""
    m = dims(cfg)
    return 3 * m.d * m.ff


def moe_layer_params(cfg: Dict) -> int:
    """Parameters of one expert layer's FFN: router, routed and shared
    experts."""
    m = dims(cfg)
    return m.d * m.experts + (m.experts + m.shared) * expert_params(cfg)


def params(cfg: Dict) -> int:
    """Parameters of the configuration: embedding, layers, final norm and
    head."""
    m = dims(cfg)
    per_layer = attention_params(cfg) + 2 * m.d
    dense = 3 * m.d * m.ff_dense
    moe = m.layers - m.dense_layers
    return (2 * m.vocab * m.d + m.d + m.layers * per_layer
            + m.dense_layers * dense + moe * moe_layer_params(cfg))


def touched_experts(cfg: Dict, tokens: int) -> float:
    """Routed experts that ``tokens`` tokens touch, expected under uniform
    routing of ``top_k`` distinct experts a token."""
    m = dims(cfg)
    return m.experts * (1.0 - (1.0 - m.top_k / m.experts) ** tokens)


def moe(cfg: Dict, *, tokens: int) -> Tuple[float, float]:
    """(operations, bytes) of the expert layers of a step over ``tokens``
    tokens: router, the routed SwiGLUs of the touched experts, the shared
    ones, and the layer's input and output rows."""
    m = dims(cfg)
    layers = m.layers - m.dense_layers
    flops = tokens * (2 * m.d * m.experts
                      + (m.top_k + m.shared) * 2 * expert_params(cfg))
    weights = (m.d * m.experts
               + (touched_experts(cfg, tokens) + m.shared)
               * expert_params(cfg))
    return (float(layers * flops),
            float(BF16 * layers * (weights + 2 * tokens * m.d)))


def _attention_matmul(cfg: Dict) -> int:
    return attention_params(cfg) - dims(cfg).latent


def _latent_values(cfg: Dict) -> int:
    """Values that one position adds to one layer's latent cache."""
    m = dims(cfg)
    return m.latent + m.rope


def mla_decode(cfg: Dict, *, batch: int, filled: int) -> Tuple[float, float]:
    """(operations, bytes) of the latent attention of a decode step, every
    layer, in the absorbed form: the new token attends to ``filled + 1``
    positions of ``kv_lora_rank + qk_rope_head_dim`` values."""
    m = dims(cfg)
    attended = filled + 1
    flops = batch * (2 * _attention_matmul(cfg)
                     + 2 * m.heads * (2 * m.latent + m.rope) * attended)
    nbytes = (attention_params(cfg) + 2 * batch * m.d
              + batch * _latent_values(cfg) * attended)
    return float(m.layers * flops), float(BF16 * m.layers * nbytes)


def _mla_prefill(cfg: Dict, *, batch: int, prompt_len: int
                 ) -> Tuple[float, float]:
    m = dims(cfg)
    tokens = batch * prompt_len
    pairs = batch * prompt_len * (prompt_len + 1) // 2
    flops = (2 * _attention_matmul(cfg) * tokens
             + 2 * m.heads * (m.nope + m.rope + m.v) * pairs)
    nbytes = (attention_params(cfg) + 2 * tokens * m.d
              + tokens * _latent_values(cfg))
    return float(m.layers * flops), float(BF16 * m.layers * nbytes)


def _rest(cfg: Dict, *, tokens: int, rows: int) -> Tuple[float, float]:
    """The dense layers' MLPs, the norms, the embedding rows and the head
    over ``rows`` last positions."""
    m = dims(cfg)
    flops = (m.dense_layers * tokens * 2 * 3 * m.d * m.ff_dense
             + 2 * rows * m.d * m.vocab)
    weights = (m.dense_layers * 3 * m.d * m.ff_dense + 2 * m.layers * m.d
               + m.d * m.vocab + m.d + tokens * m.d)
    return float(flops), float(BF16 * weights)


def decode_step(cfg: Dict, *, batch: int, filled: int) -> Tuple[float, float]:
    """(operations, bytes) of one decode step of ``batch`` sequences whose
    caches hold ``filled`` positions before the step."""
    parts = (mla_decode(cfg, batch=batch, filled=filled),
             moe(cfg, tokens=batch), _rest(cfg, tokens=batch, rows=batch))
    return (sum(p[0] for p in parts), sum(p[1] for p in parts))


def moe_decode(cfg: Dict, *, batch: int) -> Tuple[float, float]:
    """(operations, bytes) of the expert layers of one decode step."""
    return moe(cfg, tokens=batch)


def moe_prefill(cfg: Dict, *, batch: int, prompt_len: int
                ) -> Tuple[float, float]:
    """(operations, bytes) of the expert layers of a prefill."""
    return moe(cfg, tokens=batch * prompt_len)


def prefill(cfg: Dict, *, batch: int, prompt_len: int) -> Tuple[float, float]:
    """(operations, bytes) of the prefill of ``batch`` prompts of
    ``prompt_len`` tokens, causal, with logits for the last position."""
    tokens = batch * prompt_len
    parts = (_mla_prefill(cfg, batch=batch, prompt_len=prompt_len),
             moe(cfg, tokens=tokens), _rest(cfg, tokens=tokens, rows=batch))
    return (sum(p[0] for p in parts), sum(p[1] for p in parts))
