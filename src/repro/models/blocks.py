"""Block kinds: init / state-init / apply, dispatched by kind string.

A block is the unit of the layer pattern (config.group/tail).  All apply
functions share the signature::

    apply_block(kind, cfg, p, x, st, *, q_pos, ctx, mode, causal, exec_cfg)
        -> (x, new_state, aux_loss)

``st`` is the block's cache/state ({} for stateless train-mode attention);
``ctx`` is the cross-attention context embeddings (B, N, D) when present.
In decode on a carried stack (``layer`` given), ``st`` is the stage's
stacked state and the block returns that stack with what the step changed
written in place at ``layer``: one cache slot for attention, nothing for the
cross-attention context, the whole (small) state for recurrent kinds.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import attn_apply, attn_init, cross_kv, init_attn_cache
from .config import ExecConfig, ModelConfig
from .layers import ffn_apply, ffn_init, norm_apply, norm_init
from .moe import moe_apply, moe_init
from .rglru import init_rglru_state, rglru_apply, rglru_init
from .rwkv6 import init_rwkv_state, rwkv_apply, rwkv_init

__all__ = ["init_block", "init_block_state", "apply_block", "BLOCK_KINDS"]

BLOCK_KINDS = ("attn", "cross", "rwkv", "rglru")


def _ffn_params(rng, cfg: ModelConfig):
    if cfg.ffn == "moe":
        return moe_init(rng, cfg)
    return ffn_init(rng, cfg.ffn, cfg.d_model, cfg.d_ff)


def _apply_ffn(cfg: ModelConfig, p, x):
    with jax.named_scope("ffn"):
        if cfg.ffn == "moe":
            return moe_apply(cfg, p, x)
        return ffn_apply(cfg.ffn, p, x), jnp.zeros((), jnp.float32)


def init_block(kind: str, rng, cfg: ModelConfig) -> dict:
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    d = cfg.d_model
    if kind == "attn":
        return {
            "norm1": norm_init(cfg.norm, d),
            "attn": attn_init(k1, cfg),
            "norm2": norm_init(cfg.norm, d),
            "ffn": _ffn_params(k2, cfg),
        }
    if kind == "cross":
        return {
            "norm1": norm_init(cfg.norm, d),
            "attn": attn_init(k1, cfg),
            "normx": norm_init(cfg.norm, d),
            "xattn": attn_init(k3, cfg),
            "norm2": norm_init(cfg.norm, d),
            "ffn": _ffn_params(k2, cfg),
        }
    if kind == "rwkv":
        return rwkv_init(k1, cfg)
    if kind == "rglru":
        return {
            "norm1": norm_init(cfg.norm, d),
            "rec": rglru_init(k1, cfg),
            "norm2": norm_init(cfg.norm, d),
            "ffn": ffn_init(k2, cfg.ffn if cfg.ffn != "moe" else "geglu", d, cfg.d_ff),
        }
    raise ValueError(f"unknown block kind {kind}")


def init_block_state(
    kind: str, cfg: ModelConfig, batch: int, max_len: int, mode: str, *, window: int
) -> dict:
    """State/cache for one block instance.  Train mode: only recurrent kinds
    carry state (zero-init); attention needs none."""
    if kind == "attn":
        if mode == "train":
            return {}
        return {"kv": init_attn_cache(cfg, batch, max_len, window=window)}
    if kind == "cross":
        if mode == "train":
            return {}
        n_ctx = cfg.ctx_tokens
        dt = jnp.dtype(cfg.compute_dtype)
        return {
            "kv": init_attn_cache(cfg, batch, max_len, window=window),
            "xk": jnp.zeros((batch, cfg.n_kv_heads, n_ctx, cfg.d_head), dt),
            "xv": jnp.zeros((batch, cfg.n_kv_heads, n_ctx, cfg.d_head), dt),
        }
    if kind == "rwkv":
        return init_rwkv_state(cfg, batch)
    if kind == "rglru":
        st = init_rglru_state(cfg, batch)
        return st
    raise ValueError(f"unknown block kind {kind}")


def apply_block(
    kind: str,
    cfg: ModelConfig,
    p: dict,
    x: jnp.ndarray,
    st: Optional[dict],
    *,
    q_pos: jnp.ndarray,
    ctx: Optional[jnp.ndarray],
    mode: str,
    causal: bool,
    exec_cfg: ExecConfig,
    layer=None,
) -> Tuple[jnp.ndarray, dict, jnp.ndarray]:
    zero = jnp.zeros((), jnp.float32)
    st = st or {}
    if layer is not None and kind in ("rwkv", "rglru"):
        own = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False), st)
        x, new, aux = apply_block(kind, cfg, p, x, own, q_pos=q_pos, ctx=ctx, mode=mode,
                                  causal=causal, exec_cfg=exec_cfg)
        return x, jax.tree.map(
            lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n, layer, 0), st, new), aux
    if kind in ("attn", "cross"):
        h, new_kv = attn_apply(
            cfg,
            p["attn"],
            norm_apply(cfg.norm, p["norm1"], x),
            q_pos=q_pos,
            cache=st.get("kv"),
            causal=causal,
            window=cfg.window,
            layer=layer,
        )
        x = x + h
        new_st = {"kv": new_kv} if new_kv is not None else {}
        if kind == "cross":
            if mode == "decode":  # the context's K/V never change in decode
                new_st["xk"], new_st["xv"] = st["xk"], st["xv"]
                xkv = (st["xk"], st["xv"]) if layer is None else tuple(
                    jax.lax.dynamic_index_in_dim(a, layer, keepdims=False)
                    for a in (st["xk"], st["xv"]))
            else:
                xkv = cross_kv(cfg, p["xattn"], ctx)
                if mode != "train":
                    new_st["xk"], new_st["xv"] = xkv
            h, _ = attn_apply(
                cfg,
                p["xattn"],
                norm_apply(cfg.norm, p["normx"], x),
                q_pos=q_pos,
                kv=xkv,
                causal=False,
                rope=False,
            )
            x = x + h
        h, aux = _apply_ffn(cfg, p["ffn"], norm_apply(cfg.norm, p["norm2"], x))
        return x + h, new_st, aux

    if kind == "rwkv":
        y, new_st = rwkv_apply(cfg, p, x, st, exec_cfg=exec_cfg)
        return y, new_st, zero

    if kind == "rglru":
        h, new_st = rglru_apply(cfg, p["rec"], norm_apply(cfg.norm, p["norm1"], x), st)
        x = x + h
        h = ffn_apply(
            cfg.ffn if cfg.ffn != "moe" else "geglu",
            p["ffn"],
            norm_apply(cfg.norm, p["norm2"], x),
        )
        return x + h, new_st, zero

    raise ValueError(f"unknown block kind {kind}")
