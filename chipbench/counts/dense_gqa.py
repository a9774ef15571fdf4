"""Operations and bytes of one served step of a dense GQA decoder.

Counted from the configuration's shapes (the keys of ``configs/*.json``),
as the algorithm needs them at the compute dtype, bfloat16: every weight is
read once a step at 2 bytes a parameter, whatever the program stores; the
KV cache is read over the positions filled and written for the new ones,
at 2 bytes a value. A matrix product of an m x k by a k x n matrix is 2mkn
operations. Norms, rotary embeddings, softmax and the sampling argmax are
left out (a few per cent of a decode step's operations at most, no bytes
beyond the weights').
"""

from __future__ import annotations

from typing import Dict, Tuple

BF16 = 2


def _dims(cfg: Dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return d, h, kv, hd, cfg["intermediate_size"], cfg["vocab_size"]


def layer_params(cfg: Dict) -> int:
    """Parameters of one layer: q, k, v, o, the SwiGLU MLP, two norms."""
    d, h, kv, hd, ff, _ = _dims(cfg)
    return d * hd * (h + 2 * kv) + h * hd * d + 3 * d * ff + 2 * d


def _kv_values(cfg: Dict) -> int:
    """K and V values that one position adds to one layer's cache."""
    _, _, kv, hd, _, _ = _dims(cfg)
    return 2 * kv * hd


def decode_step(cfg: Dict, *, batch: int, filled: int) -> Tuple[float, float]:
    """(operations, bytes) of one decode step of ``batch`` sequences whose
    caches hold ``filled`` positions before the step: the new token
    attends to ``filled + 1`` positions."""
    d, h, kv, hd, ff, vocab = _dims(cfg)
    layers = cfg["num_hidden_layers"]
    matmul = layer_params(cfg) - 2 * d
    attended = filled + 1
    flops = layers * batch * (2 * matmul + 4 * h * hd * attended)
    flops += 2 * batch * d * vocab
    weights = layers * layer_params(cfg) + d * vocab + d + batch * d
    cache = layers * batch * _kv_values(cfg) * (filled + 1)
    return float(flops), float(BF16 * (weights + cache))


def prefill(cfg: Dict, *, batch: int, prompt_len: int) -> Tuple[float, float]:
    """(operations, bytes) of the prefill of ``batch`` prompts of
    ``prompt_len`` tokens, causal, with logits for the last position."""
    d, h, kv, hd, ff, vocab = _dims(cfg)
    layers = cfg["num_hidden_layers"]
    matmul = layer_params(cfg) - 2 * d
    tokens = batch * prompt_len
    pairs = batch * prompt_len * (prompt_len + 1) // 2
    flops = layers * (2 * matmul * tokens + 4 * h * hd * pairs)
    flops += 2 * batch * d * vocab
    weights = layers * layer_params(cfg) + d * vocab + d + tokens * d
    cache = layers * tokens * _kv_values(cfg)
    return float(flops), float(BF16 * (weights + cache))

