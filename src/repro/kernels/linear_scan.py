"""Chunked linear-recurrence kernels (Pallas TPU): RWKV-6 WKV and RG-LRU.

Both kernels keep the recurrent state VMEM-resident across a sequential
chunk grid — the TPU analogue of the GPU "chunked scan" kernels (fla /
flash-linear-attention): HBM traffic is one pass over the sequence while the
O(state) carry never leaves VMEM.  ``chunk`` (the tile length) is the
PATSMA-tunable parameter.

rwkv_scan: per (batch·head, chunk) tile, the intra-chunk term uses exact
log-space cumulative-decay differences (all exponents <= 0 — numerically
stable, no decay clamping), accumulated one source row at a time so every
value stays 2-D (Mosaic lowers no 3-D intermediates); the inter-chunk term is
one MXU matmul against the carried state.

lru_scan: first-order elementwise recurrence h_t = a_t h_{t-1} + b_t; the
in-chunk step loop is elementwise on (1, d_block) rows of the chunk staged in
f32 VMEM scratch; grid parallelism over (batch, d-blocks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["rwkv_scan_chunked", "lru_scan_chunked"]


# ------------------------------------------------------------------ RWKV-6
_HI = jax.lax.Precision.HIGHEST


def _dot(a, b, contract=((1,), (0,))):
    return jax.lax.dot_general(
        a, b, (contract, ((), ())), precision=_HI, preferred_element_type=jnp.float32
    )


def _rwkv_kernel(
    r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, y_ref, sT_ref, s_scr, c_scr, k_scr, v_scr, *, L, n_chunks
):
    nc = pl.program_id(1)

    @pl.when(nc == 0)
    def _init():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)  # (L, hd)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)  # log decay <= 0
    u = u_ref[0].astype(jnp.float32)  # (1, hd) bonus
    S = s_scr[...]  # (hd, hd)
    # f32 copies for the single-row reads below: Mosaic loads one row at a
    # dynamic offset only from an unpacked (32-bit) buffer
    k_scr[...] = k
    v_scr[...] = v

    # c_t = sum_{i<=t} lw_i, as a lower-triangular matmul (Mosaic has no cumsum)
    ti = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    c = _dot(jnp.where(ti >= si, 1.0, 0.0), lw)  # (L, hd), decreasing
    c_scr[...] = c
    cp = c - lw  # c_{t-1}
    # inter-chunk: y_t = (r_t e^{c_{t-1}}) @ S; bonus: (r_t u . k_t) v_t
    y = _dot(r * jnp.exp(cp), S) + jnp.sum(r * u * k, axis=-1, keepdims=True) * v

    # intra-chunk, one source row s at a time so every value stays 2-D:
    # y_t += (sum_i r_ti k_si e^{c_{t-1,i} - c_si}) v_s for t > s, with every
    # exponent <= 0 (exact and stable under any decay)
    t_col = jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0)

    def source(s, y):
        row = pl.ds(s, 1)
        w = jnp.where(t_col > s, jnp.exp(jnp.minimum(cp - c_scr[row, :], 0.0)), 0.0)
        score = jnp.sum(w * r * k_scr[row, :], axis=-1, keepdims=True)
        return y + score * v_scr[row, :]

    y = jax.lax.fori_loop(0, L, source, y)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: S' = diag(e^{c_L}) S + (k e^{c_L - c}).T @ v
    c_last = c[L - 1 : L, :]  # (1, hd)
    hd = S.shape[0]
    di = jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 0)
    dj = jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 1)
    decay = jnp.where(di == dj, jnp.exp(c_last), 0.0)
    s_scr[...] = _dot(decay, S) + _dot(k * jnp.exp(c_last - c), v, ((0,), (0,)))

    @pl.when(nc == n_chunks - 1)
    def _emit():
        sT_ref[0] = s_scr[...]


def rwkv_scan_chunked(r, k, v, lw, u, s0, *, chunk: int = 64, interpret: bool = False):
    """r,k,v,lw: (B,T,H,hd); u: (H,hd); s0: (B,H,hd,hd) fp32.
    Returns y: (B,T,H,hd), sT: (B,H,hd,hd)."""
    B, T, H, hd = r.shape
    L = min(chunk, T)
    if T % L:
        raise ValueError(f"T={T} not divisible by chunk={L}")
    n_chunks = T // L
    BH = B * H

    def flat(x):  # (B,T,H,hd) -> (BH, n_chunks, L, hd) row-major per head
        return x.transpose(0, 2, 1, 3).reshape(BH, n_chunks, L, hd)

    rf, kf, vf, lwf = flat(r), flat(k), flat(v), flat(lw)
    uf = jnp.broadcast_to(u[None], (B, H, hd)).reshape(BH, 1, hd)
    s0f = s0.reshape(BH, hd, hd)
    grid = (BH, n_chunks)
    y, sT = pl.pallas_call(
        functools.partial(_rwkv_kernel, L=L, n_chunks=n_chunks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, L, hd), lambda bh, nc: (bh, nc, 0, 0)),
            pl.BlockSpec((1, 1, L, hd), lambda bh, nc: (bh, nc, 0, 0)),
            pl.BlockSpec((1, 1, L, hd), lambda bh, nc: (bh, nc, 0, 0)),
            pl.BlockSpec((1, 1, L, hd), lambda bh, nc: (bh, nc, 0, 0)),
            pl.BlockSpec((1, 1, hd), lambda bh, nc: (bh, 0, 0)),
            pl.BlockSpec((1, hd, hd), lambda bh, nc: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, hd), lambda bh, nc: (bh, nc, 0, 0)),
            pl.BlockSpec((1, hd, hd), lambda bh, nc: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, n_chunks, L, hd), r.dtype),
            jax.ShapeDtypeStruct((BH, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)] + [pltpu.VMEM((L, hd), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(rf, kf, vf, lwf, uf, s0f)
    y = y.reshape(B, H, T, hd).transpose(0, 2, 1, 3)
    return y, sT.reshape(B, H, hd, hd)


# ------------------------------------------------------------------ RG-LRU
def _lru_kernel(a_ref, b_ref, h0_ref, y_ref, hT_ref, h_scr, a_scr, b_scr, y_scr, *, L, n_chunks):
    nc = pl.program_id(2)

    @pl.when(nc == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    # the step loop reads and writes one row at a dynamic offset, which Mosaic
    # allows only on unpacked (32-bit) buffers: stage the chunk in f32 scratch
    a_scr[...] = a_ref[0].astype(jnp.float32)  # (L, bd)
    b_scr[...] = b_ref[0].astype(jnp.float32)

    def step(t, h):  # h: (1, bd)
        row = pl.ds(t, 1)
        h = a_scr[row, :] * h + b_scr[row, :]
        y_scr[row, :] = h
        return h

    h = jax.lax.fori_loop(0, L, step, h_scr[...])
    h_scr[...] = h
    y_ref[0] = y_scr[...].astype(y_ref.dtype)

    @pl.when(nc == n_chunks - 1)
    def _emit():
        hT_ref[0] = h.astype(hT_ref.dtype)


def lru_scan_chunked(a, b, h0, *, chunk: int = 128, block_d: int = 512, interpret: bool = False):
    """a,b: (B,T,D); h0: (B,D) -> (hs: (B,T,D), hT: (B,D))."""
    B, T, D = a.shape
    L = min(chunk, T)
    if T % L:
        raise ValueError(f"T={T} not divisible by chunk={L}")
    bd = min(block_d, D)
    if D % bd:
        raise ValueError(f"D={D} not divisible by block_d={bd}")
    n_chunks = T // L
    grid = (B, D // bd, n_chunks)
    hs, hT = pl.pallas_call(
        functools.partial(_lru_kernel, L=L, n_chunks=n_chunks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, L, bd), lambda ib, id_, nc: (ib, nc, id_)),
            pl.BlockSpec((1, L, bd), lambda ib, id_, nc: (ib, nc, id_)),
            pl.BlockSpec((1, 1, bd), lambda ib, id_, nc: (ib, 0, id_)),
        ],
        out_specs=[
            pl.BlockSpec((1, L, bd), lambda ib, id_, nc: (ib, nc, id_)),
            pl.BlockSpec((1, 1, bd), lambda ib, id_, nc: (ib, 0, id_)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, D), a.dtype),
            jax.ShapeDtypeStruct((B, 1, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)] + [pltpu.VMEM((L, bd), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b, h0.reshape(B, 1, D))
    return hs, hT.reshape(B, D)
