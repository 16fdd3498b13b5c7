"""Plain float32 Qwen2 decoder layer (arXiv:2407.10671; the published
Qwen2-7B config.json), over a whole teacher-forced sequence:

    h = RMSNorm(x);  q, k, v = h Wq + bq, h Wk + bk, h Wv + bv
    q, k = RoPE(q), RoPE(k)          rotate-half pairs (i, i + hd/2), theta
    o = softmax(q k^T / sqrt(hd) + causal mask) v,  kv head of q head j: j // (H / Kh)
    x = x + o Wo
    x = x + (silu(RMSNorm(x) Wg) * (RMSNorm(x) Wi)) Wo_ffn

RMSNorm eps 1e-6 (``rms_norm_eps``).  Queries are taken in blocks so the
score matrix of a long prompt fits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.lm import rmsnorm

PREFIX = "stages/0/pos0/"
EPS = 1e-6
Q_BLOCK = 512


def layer_shapes(cfg: dict) -> dict:
    d, H, Kh = int(cfg["d_model"]), int(cfg["n_heads"]), int(cfg["n_kv_heads"])
    hd, ff = int(cfg.get("d_head") or d // H), int(cfg["d_ff"])
    return {
        "norm1/scale": (d,),
        "attn/wq/w": (d, H * hd), "attn/wq/b": (H * hd,),
        "attn/wk/w": (d, Kh * hd), "attn/wk/b": (Kh * hd,),
        "attn/wv/w": (d, Kh * hd), "attn/wv/b": (Kh * hd,),
        "attn/wo/w": (H * hd, d),
        "norm2/scale": (d,),
        "ffn/wg": (d, ff), "ffn/wi": (d, ff), "ffn/wo": (ff, d),
    }


def rope(x, theta: float):
    """x: (B, S, heads, hd) at positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / hd)
    ang = jnp.asarray(np.arange(S)[:, None] * inv[None, :], jnp.float32)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def apply(cfg: dict, w: dict, x, arith):
    B, S, d = x.shape
    H, Kh = int(cfg["n_heads"]), int(cfg["n_kv_heads"])
    hd = int(cfg.get("d_head") or d // H)
    g = H // Kh
    h = rmsnorm(x, w["norm1/scale"], EPS)
    q = arith.einsum("bsd,de->bse", h, w["attn/wq/w"]) + w["attn/wq/b"]
    k = arith.einsum("bsd,de->bse", h, w["attn/wk/w"]) + w["attn/wk/b"]
    v = arith.einsum("bsd,de->bse", h, w["attn/wv/w"]) + w["attn/wv/b"]
    theta = float(cfg["rope_theta"])
    q = rope(q.reshape(B, S, H, hd), theta)
    k = jnp.repeat(rope(k.reshape(B, S, Kh, hd), theta), g, axis=2)
    v = jnp.repeat(v.reshape(B, S, Kh, hd), g, axis=2)
    outs = []
    for i0 in range(0, S, Q_BLOCK):
        i1 = min(S, i0 + Q_BLOCK)
        s = arith.einsum("bqhe,bkhe->bhqk", q[:, i0:i1], k[:, :i1]) / np.sqrt(hd)
        causal = np.arange(i0, i1)[:, None] >= np.arange(i1)[None, :]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(arith.einsum("bhqk,bkhe->bqhe", p, v[:, :i1]))
    o = jnp.concatenate(outs, axis=1).reshape(B, S, H * hd)
    x = x + arith.einsum("bse,ed->bsd", o, w["attn/wo/w"])
    h = rmsnorm(x, w["norm2/scale"], EPS)
    gate = jax.nn.silu(arith.einsum("bsd,df->bsf", h, w["ffn/wg"]))
    up = arith.einsum("bsd,df->bsf", h, w["ffn/wi"])
    return x + arith.einsum("bsf,fd->bsd", gate * up, w["ffn/wo"])
