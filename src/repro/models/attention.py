"""Attention: GQA/MQA/MHA, causal + sliding-window + cross, KV cache.

Cache layout (per attention instance):
    {"k": (B, Kh, W, hd), "v": (B, Kh, W, hd), "pos": (W,) int32}
``W`` = window size for local-attention layers (ring buffer) else max
sequence length.  ``pos`` holds the absolute position stored in each slot
(-1 = empty), which drives causal/window masking uniformly across train /
prefill / decode.  Batched serving advances all rows in lockstep (one shared
position per step) — the standard batched-decode regime.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.parallel.api import current_rules

from .config import ModelConfig
from .layers import linear_apply, linear_init, rope_apply

__all__ = ["attn_init", "attn_apply", "init_attn_cache", "cross_kv"]

NEG_INF = -1e30


def attn_init(rng, cfg: ModelConfig) -> dict:
    d, hd = cfg.d_model, cfg.d_head
    kq, kk, kv, ko = jax.random.split(rng, 4)
    return {
        "wq": linear_init(kq, d, cfg.n_heads * hd, bias=cfg.qkv_bias),
        "wk": linear_init(kk, d, cfg.n_kv_heads * hd, bias=cfg.qkv_bias),
        "wv": linear_init(kv, d, cfg.n_kv_heads * hd, bias=cfg.qkv_bias),
        "wo": linear_init(ko, cfg.n_heads * hd, d),
    }


def init_attn_cache(cfg: ModelConfig, batch: int, max_len: int, *, window: int = 0) -> dict:
    w = min(window, max_len) if window > 0 else max_len
    shape = (batch, cfg.n_kv_heads, w, cfg.d_head)
    dt = jnp.dtype(cfg.compute_dtype)
    return {
        "k": jnp.zeros(shape, dt),
        "v": jnp.zeros(shape, dt),
        "pos": jnp.full((w,), -1, jnp.int32),
    }


@jax.named_scope("kv_write")
def _ring_write(cache: dict, kc, vc, q_pos, layer=None) -> dict:
    """Store the n entries at consecutive absolute positions ``q_pos`` in the
    ring of W slots, entry i at slot (q_pos[0] + i) mod W.  n == W rewrites
    the whole ring (a rotation); n < W is one contiguous write, which never
    wraps here: a prefill starts at position 0 and a decode step writes one
    slot.  With ``layer``, ``cache`` is a stage's stack of caches (leading
    layer dim) and the entries go to that layer's ring, in place.  Slice
    updates rather than a scatter: the TPU compiler aborts on the scatter
    form of this write."""
    W = cache["pos"].shape[-1]
    s0 = jnp.mod(q_pos[0], W).astype(jnp.int32)
    pos = q_pos.astype(jnp.int32)
    if layer is None and kc.shape[2] == W:
        return {"k": jnp.roll(kc.astype(cache["k"].dtype), s0, axis=2),
                "v": jnp.roll(vc.astype(cache["v"].dtype), s0, axis=2),
                "pos": jnp.roll(pos, s0)}
    z = jnp.int32(0)
    lead = () if layer is None else (layer,)

    def put(buf, new, start):
        return jax.lax.dynamic_update_slice(
            buf, new.astype(buf.dtype).reshape((1,) * len(lead) + new.shape), lead + start)

    return {
        "k": put(cache["k"], kc, (z, z, s0, z)),
        "v": put(cache["v"], vc, (z, z, s0, z)),
        "pos": put(cache["pos"], pos, (s0,)),
    }


def _mask(q_pos, kv_pos, *, causal: bool, window: int):
    """(Sq, Skv) bool validity mask from absolute positions."""
    valid = kv_pos[None, :] >= 0
    if causal:
        valid &= kv_pos[None, :] <= q_pos[:, None]
    if window > 0:
        valid &= q_pos[:, None] - kv_pos[None, :] < window
    return valid


def _sdpa(q, k, v, mask) -> jnp.ndarray:
    """q: (B,Sq,H,hd), k/v: (B,Kh,Skv,hd), mask: (Sq,Skv) -> (B,Sq,H,hd).
    fp32 softmax; GQA via head grouping."""
    # NOTE (§Perf iteration 1, REFUTED hypothesis): explicit sharding
    # constraints on the S² chain were tried here and changed nothing — A/B
    # showed GSPMD already shards scores over (dp × heads); the term is big
    # because S² itself is big.  The fix is the flash kernel
    # (kernels/flash_attention.py), which cached prefill takes on a TPU
    # (_prefill_takes_flash).
    B, Sq, H, hd = q.shape
    Kh = k.shape[1]
    g = H // Kh
    qh = q.reshape(B, Sq, Kh, g, hd)
    scores = jnp.einsum("bqkgh,bksh->bkgqs", qh, k, preferred_element_type=jnp.float32)
    scores = scores * (1.0 / np.sqrt(hd))
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bksh->bqkgh", p, v)
    return out.reshape(B, Sq, H, hd)


def _sdpa_flash(q, k, v) -> jnp.ndarray:
    """Causal self-attention over a whole prompt through the Pallas flash
    kernel: query and key i at position i.  Interpret mode only where the
    platform cannot run the kernel compiled (the CPU)."""
    from repro.kernels import ops as kops

    return kops.flash_attention(q, k, v, causal=True, interpret=kops.interpret_mode())


def _prefill_takes_flash(causal: bool, window: int) -> bool:
    """Whether a cached prefill runs the flash kernel: causal self-attention
    over the whole prompt (no window) in a program built for one TPU device
    (``kernels.ops.target_platform``).  The S x S scores then stay on chip.
    Everything else keeps ``_sdpa``, a program sharded over several devices
    too: the partitioner cannot split a Pallas call."""
    if not causal or window > 0:
        return False
    ctx = current_rules()
    if ctx is not None and ctx[0].size > 1:
        return False
    from repro.kernels import ops as kops

    return kops.target_platform() == "tpu"


def cross_kv(cfg: ModelConfig, p: dict, ctx: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Precompute cross-attention K/V from context embeddings (B,N,D)."""
    B, N, _ = ctx.shape
    k = linear_apply(p["wk"], ctx).reshape(B, N, cfg.n_kv_heads, cfg.d_head)
    v = linear_apply(p["wv"], ctx).reshape(B, N, cfg.n_kv_heads, cfg.d_head)
    return k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)


@jax.named_scope("attn")
def attn_apply(
    cfg: ModelConfig,
    p: dict,
    x: jnp.ndarray,
    *,
    q_pos: jnp.ndarray,  # (Sq,) absolute positions of the query tokens
    cache: Optional[dict] = None,
    kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,  # cross-attn K/V
    causal: bool = True,
    window: int = 0,
    rope: bool = True,
    layer=None,
) -> Tuple[jnp.ndarray, Optional[dict]]:
    """Self- or cross-attention with optional KV cache.

    Modes:
      * train/encode: ``cache=None, kv=None`` — full-sequence self-attention.
      * prefill:      ``cache=empty`` — fills the cache, returns outputs.
      * decode:       ``cache=filled``, Sq=1 — appends one step.  With
                      ``layer``, ``cache`` is the stage's stack of caches and
                      the step writes its one slot of that layer in place.
      * cross:        ``kv=(k,v)`` precomputed from context; no cache update.
    """
    B, Sq, D = x.shape
    H, Kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = linear_apply(p["wq"], x).reshape(B, Sq, H, hd)

    if kv is not None:  # ---------------------------------------- cross-attn
        k, v = kv
        out = _sdpa(q, k, v, jnp.ones((Sq, k.shape[2]), bool))
        return linear_apply(p["wo"], out.reshape(B, Sq, H * hd)), cache

    kc = linear_apply(p["wk"], x).reshape(B, Sq, Kh, hd)
    vc = linear_apply(p["wv"], x).reshape(B, Sq, Kh, hd)
    if rope:
        q = rope_apply(q, q_pos, cfg.rope_theta)
        kc = rope_apply(kc, q_pos, cfg.rope_theta)
    kc = kc.transpose(0, 2, 1, 3)  # (B,Kh,Sq,hd)
    vc = vc.transpose(0, 2, 1, 3)

    if cache is None:  # ------------------------------------- train / encode
        out = _sdpa(q, kc, vc, _mask(q_pos, q_pos, causal=causal, window=window))
        return linear_apply(p["wo"], out.reshape(B, Sq, H * hd)), None

    # ------------------------------------------------- prefill / decode step
    W = cache["pos"].shape[-1]
    if Sq > 1:
        # prefill: attend over the in-flight full sequence (correct even when
        # Sq > W), then persist only the last W entries into the ring.  The
        # counters count lowerings (a layer scan traces its body once).
        flash = _prefill_takes_flash(causal, window)
        obs.counter("attn.prefill_lowered." + ("flash" if flash else "xla")).inc()
        if flash:
            out = _sdpa_flash(q, kc, vc)
        else:
            out = _sdpa(q, kc, vc, _mask(q_pos, q_pos, causal=causal, window=window))
        if Sq > W:
            kc, vc, q_pos = kc[:, :, Sq - W :], vc[:, :, Sq - W :], q_pos[Sq - W :]
        new_cache = _ring_write(cache, kc, vc, q_pos)
        return linear_apply(p["wo"], out.reshape(B, Sq, H * hd)), new_cache

    # decode: attend over the ring with the step's entry in its slot, then
    # write the entry.  The attention reads the cache as it stood, with the
    # entry selected in as it is read, so XLA keeps one layout for a stack
    # carried through the layer scan (``layer``) and updates it in place.
    own = cache if layer is None else jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False), cache)
    slot = jnp.arange(W) == jnp.mod(q_pos[0], W)
    k_att = jnp.where(slot[:, None], kc.astype(own["k"].dtype), own["k"])
    v_att = jnp.where(slot[:, None], vc.astype(own["v"].dtype), own["v"])
    mask = _mask(q_pos, jnp.where(slot, q_pos[0], own["pos"]), causal=causal, window=window)
    out = _sdpa(q, k_att, v_att, mask)
    new_cache = _ring_write(cache, kc, vc, q_pos, layer)
    return linear_apply(p["wo"], out.reshape(B, Sq, H * hd)), new_cache
