"""Flash-attention forward kernel (Pallas TPU): online-softmax over KV tiles.

TPU adaptation of the FlashAttention insight (arXiv:2205.14135): stream KV
through VMEM in ``block_kv`` tiles while a ``block_q`` query tile and the
(m, l, acc) online-softmax carry stay VMEM-resident; MXU does the two
matmuls per tile.  Grid = (B, H, nQ, nKV) with the KV dimension sequential
("arbitrary") so the carry persists in scratch across KV tiles.

``block_q`` / ``block_kv`` are the PATSMA-tunable parameters (the paper's
OpenMP-chunk analogue).  Causal masking skips fully-masked KV tiles.
GQA: query head h reads KV head h // (H // Kh).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["flash_attention_fwd"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, causal, scale, block_q, block_kv, n_kv):
    iq = pl.program_id(2)
    ikv = pl.program_id(3)

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    kv_start = ikv * block_kv

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (bkv, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bkv)
        if causal:
            qi = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
            kj = kv_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(qi >= kj, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)  # (bkv, hd)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    if causal:
        # causal tile skip: run only tiles not entirely in the future
        pl.when(kv_start <= q_start + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ikv == n_kv - 1)
    def _emit():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_fwd(
    q, k, v, *, causal: bool = True, block_q: int = 128, block_kv: int = 128,
    interpret: bool = False,
):
    """q: (B,H,Sq,hd); k/v: (B,Kh,Skv,hd) -> o: (B,H,Sq,hd)."""
    B, H, Sq, hd = q.shape
    Kh, Skv = k.shape[1], k.shape[2]
    g = H // Kh
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    if Sq % block_q or Skv % block_kv:
        raise ValueError(f"seq ({Sq},{Skv}) not divisible by blocks ({block_q},{block_kv})")
    n_q, n_kv = Sq // block_q, Skv // block_kv
    grid = (B, H, n_q, n_kv)
    kern = functools.partial(
        _kernel,
        causal=causal,
        scale=1.0 / np.sqrt(hd),
        block_q=block_q,
        block_kv=block_kv,
        n_kv=n_kv,
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ikv: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, iq, ikv, g=g: (b, h // g, ikv, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, iq, ikv, g=g: (b, h // g, ikv, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ikv: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
