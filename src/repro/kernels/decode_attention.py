"""Flash-decode kernel (Pallas TPU): one query token vs a long KV cache.

Streams the KV cache through VMEM in ``block_kv`` tiles with the online
softmax carry in scratch — the decode-shaped sibling of flash attention
(FlashDecoding, arXiv:2311.01282, adapted to TPU tiles).  Validity of each
cache slot comes from an explicit ``valid`` mask vector (int32 0/1), which
uniformly supports ring buffers (windowed layers) and partially-filled
caches.  ``block_kv`` is PATSMA-tunable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["decode_attention_fwd"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_scr, l_scr, acc_scr, *, scale, n_kv):
    ikv = pl.program_id(2)

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # (g, hd) — the GQA group
    k = k_ref[0, 0].astype(jnp.float32)  # (bkv, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    s = jnp.where(valid_ref[0] > 0, s, NEG_INF)  # (1, bkv) mask over (g, bkv)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    v = v_ref[0, 0].astype(jnp.float32)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ikv == n_kv - 1)
    def _emit():
        l = jnp.where(l_scr[...] == 0.0, 1.0, l_scr[...])
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def decode_attention_fwd(q, k, v, valid, *, block_kv: int = 512, interpret: bool = False):
    """q: (B,H,hd); k/v: (B,Kh,S,hd); valid: (B,S) int32 -> o: (B,H,hd).

    Layout: queries regrouped to (B, Kh, g, hd) so one grid cell handles one
    KV head's whole GQA group (g query heads share the streamed KV tiles)."""
    B, H, hd = q.shape
    Kh, S = k.shape[1], k.shape[2]
    g = H // Kh
    block_kv = min(block_kv, S)
    if S % block_kv:
        raise ValueError(f"cache length {S} not divisible by block_kv {block_kv}")
    n_kv = S // block_kv
    qg = q.reshape(B, Kh, g, hd)
    grid = (B, Kh, n_kv)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / np.sqrt(hd), n_kv=n_kv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda b, h, ikv: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, ikv: (b, h, ikv, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, ikv: (b, h, ikv, 0)),
            # (1, 1, block_kv) of the (B, 1, S) mask: a (1, block_kv) block of
            # a (B, S) array breaks the TPU's (8, 128) tiling rule for B > 1
            pl.BlockSpec((1, 1, block_kv), lambda b, h, ikv: (b, 0, ikv)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd), lambda b, h, ikv: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Kh, g, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qg, k, v, valid.reshape(B, 1, S))
    return out.reshape(B, H, hd)
