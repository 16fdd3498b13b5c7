"""Operations and bytes the serving algorithm needs, from shapes alone.

These count the work the model requires, not what the compiler emitted: a
multiply-add is two operations, causal attention over ``S`` prompt tokens
attends ``S * (S + 1) / 2`` query-key pairs, and a decode step reads each
weight once and the valid cache entries once, and writes what it appends.
A program that skips wasted work (masked cache slots, recomputed weights)
therefore raises its share and can never push it past the peak.

``cfg`` is the ``model_config`` mapping of a configuration file: the
``ModelConfig`` fields as run (``n_layers``, ``d_model``, ``n_heads``,
``n_kv_heads``, ``d_head``, ``d_ff``, ``vocab_size``, ``group``,
``param_dtype``, ``compute_dtype``).
"""
from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _dims(cfg: dict):
    d = int(cfg["d_model"])
    H = int(cfg["n_heads"])
    hd = int(cfg.get("d_head") or d // H)
    return d, H, int(cfg["n_kv_heads"]), hd, int(cfg["d_ff"]), int(cfg["vocab_size"])


def layer_kinds(cfg: dict) -> list:
    group = list(cfg.get("group") or ["attn"])
    n = int(cfg["n_layers"])
    if n % len(group):
        raise ValueError(f"{n} layers do not tile as {group}")
    return group * (n // len(group))


def layer_matmul_params(kind: str, cfg: dict) -> int:
    """Weights a token multiplies through in one layer of ``kind``."""
    d, H, Kh, hd, ff, _ = _dims(cfg)
    if kind == "attn":
        return d * H * hd + 2 * d * Kh * hd + H * hd * d + 3 * d * ff
    raise ValueError(f"no counts for layer kind {kind!r}")


def layer_params(kind: str, cfg: dict) -> int:
    """Every parameter of one layer (matmul weights, biases, norms)."""
    d, H, Kh, hd, _, _ = _dims(cfg)
    n = layer_matmul_params(kind, cfg)
    if kind == "attn":
        n += 2 * d  # two RMSNorm scales
        if cfg.get("qkv_bias"):
            n += H * hd + 2 * Kh * hd
    return n


def weight_bytes(cfg: dict) -> int:
    """Bytes of every layer weight, the final norm and the LM head."""
    d, *_, V = _dims(cfg)
    pb = _BYTES[cfg.get("param_dtype", "float32")]
    n = sum(layer_params(k, cfg) for k in layer_kinds(cfg)) + d + d * V  # RMSNorm, head
    return n * pb


def _attn_pairs_decode(pos: int) -> int:
    """Keys one decode query attends at absolute position ``pos``."""
    return pos + 1


def decode_step_flops(cfg: dict, batch: int, pos: int) -> float:
    """Operations of one decode step for ``batch`` rows at position ``pos``."""
    d, H, Kh, hd, _, V = _dims(cfg)
    per_row = 2 * d * V  # LM head
    for kind in layer_kinds(cfg):
        per_row += 2 * layer_matmul_params(kind, cfg)
        if kind == "attn":
            per_row += 4 * H * hd * _attn_pairs_decode(pos)
    return float(batch * per_row)


def decode_step_bytes(cfg: dict, batch: int, pos: int) -> float:
    """HBM bytes one decode step needs: each weight once, the embedding rows
    of the batch's tokens, the valid cache entries read once and the new
    entries written once."""
    d, H, Kh, hd, _, _ = _dims(cfg)
    pb = _BYTES[cfg.get("param_dtype", "float32")]
    cb = _BYTES[cfg.get("compute_dtype", "bfloat16")]
    n = weight_bytes(cfg) + batch * d * pb
    for kind in layer_kinds(cfg):
        if kind == "attn":
            n += 2 * batch * Kh * hd * cb * (_attn_pairs_decode(pos) + 1)
    return float(n)


def prefill_flops(cfg: dict, batch: int, seq: int) -> float:
    """Operations of a prefill of ``batch`` prompts of ``seq`` tokens plus the
    first token's logits (the LM head runs on the last position only)."""
    d, H, Kh, hd, _, V = _dims(cfg)
    per_row = 2 * d * V
    for kind in layer_kinds(cfg):
        per_row += 2 * layer_matmul_params(kind, cfg) * seq
        if kind == "attn":
            per_row += 4 * H * hd * seq * (seq + 1) // 2
    return float(batch * per_row)
