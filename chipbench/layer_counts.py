"""Bytes and operations of single layers of the model, from shapes alone,
under the conventions of ``chipbench/counts.py``: each weight read once a
step, the valid cache entries read once and the new ones written once,
causal attention over half the score matrix.  Together with the norms and
the embedding rows the decode parts add up to ``counts.decode_step_bytes``.
"""
from __future__ import annotations

from chipbench.counts import _BYTES, _attn_pairs_decode, _dims, layer_kinds


def _attn_layers(cfg: dict) -> int:
    return sum(1 for k in layer_kinds(cfg) if k == "attn")


def _attn_params(cfg: dict) -> int:
    """Projection weights and biases of one attention layer."""
    d, H, Kh, hd, _, _ = _dims(cfg)
    n = d * H * hd + 2 * d * Kh * hd + H * hd * d
    if cfg.get("qkv_bias"):
        n += H * hd + 2 * Kh * hd
    return n


def ffn_weight_bytes(cfg: dict) -> int:
    """The FFN weights of every layer (gate, up, down)."""
    d, *_, ff, _ = _dims(cfg)
    return _attn_layers(cfg) * 3 * d * ff * _BYTES[cfg.get("param_dtype", "float32")]


def attn_decode_bytes(cfg: dict, batch: int, pos: int) -> int:
    """One decode step's attention at position ``pos``: projection weights
    and biases of every layer, the valid cache entries read once and the
    new entry written once."""
    _, _, Kh, hd, _, _ = _dims(cfg)
    pb = _BYTES[cfg.get("param_dtype", "float32")]
    cb = _BYTES[cfg.get("compute_dtype", "bfloat16")]
    kv = 2 * batch * Kh * hd * cb * (_attn_pairs_decode(pos) + 1)
    return _attn_layers(cfg) * (_attn_params(cfg) * pb + kv)


def head_weight_bytes(cfg: dict) -> int:
    """The LM head's weights."""
    d, *_, V = _dims(cfg)
    return d * V * _BYTES[cfg.get("param_dtype", "float32")]


def attn_prefill_flops(cfg: dict, batch: int, seq: int) -> float:
    """Attention operations of a prefill of ``batch`` prompts of ``seq``
    tokens: the projections and the causal half of the scores and values."""
    d, H, Kh, hd, _, _ = _dims(cfg)
    per_layer = 2 * (d * H * hd + 2 * d * Kh * hd + H * hd * d) * seq
    per_layer += 4 * H * hd * seq * (seq + 1) // 2
    return float(batch * _attn_layers(cfg) * per_layer)
