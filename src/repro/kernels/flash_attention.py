"""Flash-attention forward kernel (Pallas TPU): online-softmax over KV tiles.

TPU adaptation of the FlashAttention insight (arXiv:2205.14135): stream KV
through VMEM in ``block_kv`` tiles while a query tile and the (m, l, acc)
online-softmax carry stay VMEM-resident; the MXU does the two matmuls per
tile, on the inputs' dtype with float32 accumulation (as the XLA path
feeds it), and the softmax runs in float32.

Grid = (B, Kh, nQ, nKV), the KV dimension sequential ("arbitrary") so the
carry persists in scratch across KV tiles.  A cell holds the ``block_q``
queries of all g = H // Kh query heads that share one KV head, so each K/V
tile is fetched once per query tile, not once per query head.  Causal
attention skips the compute of a KV tile entirely in the future of the
query tile, and the K/V index map repeats the last tile the query tile
needs in its place, so such a tile is not fetched either.

Lengths that are not tile multiples are padded up to them: padded keys
are masked, padded query rows are dropped.  ``block_q`` / ``block_kv``
are the PATSMA-tunable parameters (the paper's OpenMP-chunk analogue).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["flash_attention_fwd", "kv_block_index"]

NEG_INF = -1e30


def kv_block_index(iq, ikv, *, block_q: int, block_kv: int, causal: bool):
    """KV tile that query tile ``iq`` reads at step ``ikv``: under causal
    attention a tile past the last one the query tile needs repeats that
    last one, which the pipeline then does not fetch again."""
    if not causal:
        return ikv
    return jnp.minimum(ikv, ((iq + 1) * block_q - 1) // block_kv)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, causal, scale, block_q,
            block_kv, n_kv, kv_len):
    g = acc_scr.shape[0]
    iq = pl.program_id(2)
    ikv = pl.program_id(3)
    q_start = iq * block_q
    kv_start = ikv * block_kv

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute():
        k = k_ref[...]  # (bkv, hd), shared by the g query heads
        v = v_ref[...]
        qi = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        kj = kv_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        valid = kj < kv_len
        if causal:
            valid &= qi >= kj

        def head(h, carry):
            s = jax.lax.dot_general(
                q_ref[h], k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale  # (bq, bkv)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            acc_scr[h] = acc_scr[h] * alpha + pv
            m_scr[h] = m_new
            return carry

        jax.lax.fori_loop(0, g, head, None)

    if causal:  # a tile entirely in the query tile's future is skipped
        pl.when(kv_start <= q_start + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ikv == n_kv - 1)
    def _emit():
        def head(h, carry):
            l = l_scr[h]
            l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
            o_ref[h] = (acc_scr[h] / l).astype(o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, g, head, None)


def _pad_to(x, axis: int, n: int):
    if x.shape[axis] == n:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, n - x.shape[axis])
    return jnp.pad(x, pad)


def flash_attention_fwd(
    q, k, v, *, causal: bool = True, block_q: int = 1024, block_kv: int = 1024,
    interpret: bool = False,
):
    """q: (B,Sq,H,hd); k/v: (B,Kh,Skv,hd) -> o: (B,Sq,H,hd).  Query i sits
    at position i and key j at position j (causal: j <= i).  q and o pass
    the kernel head-major, (B,H,Sq,hd), so a block holds the g heads of one
    group along its leading dim."""
    B, Sq, H, hd = q.shape
    Kh, Skv = k.shape[1], k.shape[2]
    g = H // Kh
    block_q = min(block_q, _tile_cap(Sq))
    block_kv = min(block_kv, _tile_cap(Skv))
    sq_pad = -(-Sq // block_q) * block_q
    skv_pad = -(-Skv // block_kv) * block_kv
    n_q, n_kv = sq_pad // block_q, skv_pad // block_kv
    q = _pad_to(q, 1, sq_pad).transpose(0, 2, 1, 3)
    sq = pl.Squeezed()
    q_spec = pl.BlockSpec((sq, g, block_q, hd), lambda b, h, iq, ikv: (b, h, iq, 0))
    k = _pad_to(k, 2, skv_pad)
    v = _pad_to(v, 2, skv_pad)
    kern = functools.partial(
        _kernel,
        causal=causal,
        scale=1.0 / np.sqrt(hd),
        block_q=block_q,
        block_kv=block_kv,
        n_kv=n_kv,
        kv_len=Skv,
    )
    kv_idx = functools.partial(kv_block_index, block_q=block_q, block_kv=block_kv, causal=causal)
    kv_spec = pl.BlockSpec((sq, sq, block_kv, hd),
                           lambda b, h, iq, ikv: (b, h, kv_idx(iq, ikv), 0))
    itemsize = jnp.dtype(q.dtype).itemsize
    flops = 4 * B * H * Sq * Skv * hd
    out = pl.pallas_call(
        kern,
        grid=(B, Kh, n_q, n_kv),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, block_q, 1), jnp.float32),
            pltpu.VMEM((g, block_q, 1), jnp.float32),
            pltpu.VMEM((g, block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(g, block_q, block_kv, hd, itemsize),
        ),
        cost_estimate=pl.CostEstimate(
            flops=flops // 2 if causal else flops,
            transcendentals=B * H * Sq * Skv // (2 if causal else 1),
            bytes_accessed=(2 * q.size + k.size + v.size) * itemsize,
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq].transpose(0, 2, 1, 3)


def _tile_cap(n: int) -> int:
    """Largest tile worth using along a length of ``n``: the power of two
    at or above it, and no less than 128 (one MXU pass)."""
    return max(128, 1 << (n - 1).bit_length())


def _vmem_bytes(g: int, block_q: int, block_kv: int, hd: int, itemsize: int) -> int:
    """Scoped VMEM a cell asks for: double-buffered q, o, k and v blocks,
    the float32 carry (m and l padded to a lane tile) and a few float32
    score tiles of one head, with room to spare; within the v5e's 128 MiB."""
    rows = g * block_q
    blocks = 2 * (2 * rows * hd + 2 * block_kv * hd) * itemsize
    carry = 4 * rows * (hd + 2 * 128)
    scores = 4 * 4 * block_q * block_kv
    return int(min(max(2 * (blocks + carry + scores), 32 << 20), 100 << 20))
