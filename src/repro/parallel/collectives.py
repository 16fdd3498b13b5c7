"""Distributed-optimization collectives: compressed gradient reduction.

Cross-pod links are the scarcest bandwidth at 1000-node scale; these
utilities trade precision for wire bytes on the DP all-reduce:

  * ``int8_psum``      — per-tensor-scaled int8 quantized psum (≈4× fewer
                          bytes than fp32 on the wire), with stochastic-free
                          deterministic rounding;
  * ``topk_psum``      — magnitude top-k sparsification with **error
                          feedback** (the residual is carried to the next
                          step, so the compression bias vanishes over time —
                          Seide et al. / Deep Gradient Compression);
  * ``make_compressed_dp_step`` — explicit-DP train step (shard_map over the
                          data axis) wiring either compressor into the
                          gradient reduction, with the error-feedback state
                          threaded through the step signature.

The implicit-SPMD train path keeps XLA's native all-reduce; this module is
the explicit path for bandwidth-starved cross-pod reductions (tested in
tests/test_parallel.py and tests/test_launch_spaces.py).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = [
    "int8_psum",
    "topk_psum",
    "chunked_psum",
    "make_compressed_dp_step",
    "wire_bytes",
]


def int8_psum(g: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Quantize to int8 with a shared (psum-max) scale, reduce, dequantize."""
    amax = jax.lax.pmax(jnp.max(jnp.abs(g)), axis)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int32)
    s = jax.lax.psum(q, axis)
    return s.astype(jnp.float32) * scale


def topk_psum(g: jnp.ndarray, axis: str, k_ratio: float, err: jnp.ndarray):
    """Error-feedback top-k: reduce only the largest |g+err| entries.

    Returns (reduced_dense, new_err).  Wire bytes ≈ 2 * k * 8 (values+indices)
    vs n * 4 dense — here emulated with a masked dense psum (the wire-cost
    model is what the benchmark reports; a production impl would use
    sparse collectives or gather-based exchange)."""
    ge = g + err
    flat = ge.reshape(-1)
    k = max(1, int(flat.size * k_ratio))
    thresh = jax.lax.top_k(jnp.abs(flat), k)[0][-1]
    mask = (jnp.abs(ge) >= thresh).astype(g.dtype)
    sparse = ge * mask
    new_err = ge - sparse  # residual carried to the next step
    return jax.lax.psum(sparse, axis), new_err


def chunked_psum(g: jnp.ndarray, axis: str, chunk_bytes: int) -> jnp.ndarray:
    """All-reduce ``g`` in fixed-size chunks of ≤ ``chunk_bytes`` each.

    Collective chunking is a launch-level knob (``launch.spaces``): smaller
    chunks let an async scheduler overlap the reduction with compute and
    bound the per-op ICI buffer, at the price of per-chunk dispatch latency;
    one huge all-reduce is the opposite trade.  The reduction is exact — the
    result equals ``jax.lax.psum(g, axis)`` bit-for-bit in fp32 — only the
    op granularity changes (one psum per chunk via ``lax.map``)."""
    if chunk_bytes <= 0:
        raise ValueError(f"chunk_bytes must be positive, got {chunk_bytes}")
    per = max(1, int(chunk_bytes) // g.dtype.itemsize)
    flat = g.reshape(-1)
    if flat.size <= per:
        return jax.lax.psum(g, axis)
    n_chunks = -(-flat.size // per)
    pad = n_chunks * per - flat.size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n_chunks, per)
    reduced = jax.lax.map(lambda c: jax.lax.psum(c, axis), chunks)
    return reduced.reshape(-1)[: g.size].reshape(g.shape)


def wire_bytes(tree, method: str, k_ratio: float = 0.01) -> int:
    """Wire-cost model per DP all-reduce (ring: 2(n-1)/n ≈ 2x size)."""
    n = sum(x.size for x in jax.tree.leaves(tree))
    if method == "fp32":
        per = 4 * n
    elif method == "bf16":
        per = 2 * n
    elif method == "int8":
        per = 1 * n + 4
    elif method == "topk":
        per = int(n * k_ratio) * (4 + 4)  # value + index
    else:
        raise ValueError(method)
    return 2 * per


def make_compressed_dp_step(
    loss_fn: Callable,
    optimizer,
    mesh,
    *,
    axis: str = "data",
    method: str = "int8",
    k_ratio: float = 0.01,
    chunk_bytes: int = 0,
):
    """Explicit-DP train step: per-device grads on the local microbatch, then
    a compressed cross-device reduction.  Params replicated over ``axis``.

    step(params, opt_state, err_state, batch) ->
        (params, opt_state, err_state, metrics)
    """

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    nd = mesh.shape[axis]

    def local_step(params, opt_state, err, batch):
        (loss, aux), grads = grad_fn(params, batch)
        if method == "int8":
            grads = jax.tree.map(lambda g: int8_psum(g / nd, axis), grads)
            new_err = err
        elif method == "topk":
            out = jax.tree.map(
                lambda g, e: topk_psum(g / nd, axis, k_ratio, e), grads, err
            )
            grads = jax.tree.map(lambda t: t[0], out, is_leaf=lambda t: isinstance(t, tuple))
            new_err = jax.tree.map(lambda t: t[1], out, is_leaf=lambda t: isinstance(t, tuple))
        elif method == "chunked":
            grads = jax.tree.map(
                lambda g: chunked_psum(g / nd, axis, chunk_bytes or g.nbytes), grads
            )
            new_err = err
        else:  # exact
            grads = jax.tree.map(lambda g: jax.lax.psum(g / nd, axis), grads)
            new_err = err
        loss = jax.lax.pmean(loss, axis)
        params, opt_state, om = optimizer.update(grads, opt_state, params)
        return params, opt_state, new_err, dict(aux, loss=loss, **om)

    def rep(tree):
        return jax.tree.map(lambda _: P(), tree)

    def step(params, opt_state, err, batch):
        batch_specs = jax.tree.map(lambda _: P(axis), batch)
        fn = jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(rep(params), rep(opt_state), rep(err), batch_specs),
            out_specs=(rep(params), rep(opt_state), rep(err), P()),
            check_vma=False,
        )
        return fn(params, opt_state, err, batch)

    def init_err(params):
        if method == "topk":
            return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        return jax.tree.map(lambda p: jnp.zeros((), jnp.float32), params)

    return step, init_err
