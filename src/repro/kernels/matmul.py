"""Tiled matmul kernel (Pallas TPU) — the canonical block-size auto-tuning
demo (paper §2.3: "block size (or loop granularity)" as the tunable).

Grid (M/bm, N/bn, K/bk) with the K dimension sequential and an fp32
accumulator tile in VMEM.  (bm, bn, bk) are the PATSMA-tunables; MXU wants
multiples of 128 on the minor dims — the tuner discovers this itself, which
is exactly the paper's pitch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["matmul_tiled"]


def _kernel(a_ref, b_ref, o_ref, acc_scr, *, n_k):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ik == n_k - 1)
    def _emit():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def matmul_tiled(a, b, *, bm: int = 256, bn: int = 256, bk: int = 256, interpret: bool = False):
    """a: (M,K) @ b: (K,N) -> (M,N) with fp32 accumulation."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    if M % bm or N % bn or K % bk:
        raise ValueError(f"dims {(M, N, K)} not divisible by tiles {(bm, bn, bk)}")
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        functools.partial(_kernel, n_k=K // bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b)
