"""Public jit'd wrappers for the Pallas kernels.

Gradients: forward passes run the kernels; backward passes recompute through
the jnp references via ``jax.custom_vjp`` (exact — the references are the
oracles the kernels are validated against).  Writing fused backward kernels
is listed as future work in DESIGN.md; the custom-vjp split keeps training
correct on day one while the forward hot path uses the tuned kernels.

All wrappers accept ``interpret=True`` so the kernel *bodies* execute on the
CPU backend for validation; :func:`interpret_mode` is that choice made from
the platform, and :func:`target_platform` the platform itself.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools

import jax
import jax.numpy as jnp

from . import ref
from .decode_attention import decode_attention_fwd
from .flash_attention import flash_attention_fwd
from .linear_scan import lru_scan_chunked, rwkv_scan_chunked
from .matmul import matmul_tiled

__all__ = [
    "flash_attention", "decode_attention", "rwkv_scan", "lru_scan", "matmul",
    "interpret_mode", "target_platform", "targeting",
]

_TARGET: contextvars.ContextVar = contextvars.ContextVar("kernel_target", default=None)


def target_platform() -> str:
    """The platform that programs traced now are built for: the default
    backend, or the one a :func:`targeting` block names."""
    return _TARGET.get() or jax.default_backend()


@contextlib.contextmanager
def targeting(platform: str):
    """Trace for ``platform`` (e.g. ``"tpu"``) rather than the default
    backend: a program compiled for a described chip from a host without one."""
    token = _TARGET.set(platform)
    try:
        yield
    finally:
        _TARGET.reset(token)


def interpret_mode() -> bool:
    """Whether kernels run in Pallas interpret mode: only on the CPU,
    which cannot run them compiled.  Every other platform runs them compiled."""
    return target_platform() == "cpu"


# ---------------------------------------------------------- flash attention
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_kv, interpret):
    return flash_attention_fwd(
        q, k, v, causal=causal, block_q=block_q, block_kv=block_kv, interpret=interpret
    )


def _flash_fwd(q, k, v, causal, block_q, block_kv, interpret):
    return _flash(q, k, v, causal, block_q, block_kv, interpret), (q, k, v)


def _flash_bwd(causal, block_q, block_kv, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: ref.flash_attention_ref(q_, k_, v_, causal=causal), q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal=True, block_q=1024, block_kv=1024, interpret=False):
    """q: (B,Sq,H,hd); k/v: (B,Kh,Skv,hd) -> (B,Sq,H,hd)."""
    return _flash(q, k, v, causal, block_q, block_kv, interpret)


# ---------------------------------------------------------- decode attention
def decode_attention(q, k, v, valid, *, block_kv=512, interpret=False):
    """Inference-only (no vjp needed). q: (B,H,hd); k/v: (B,Kh,S,hd);
    valid: (B,S) int32."""
    return decode_attention_fwd(q, k, v, valid, block_kv=block_kv, interpret=interpret)


# ----------------------------------------------------------------- rwkv scan
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _rwkv(r, k, v, lw, u, s0, chunk, interpret):
    return rwkv_scan_chunked(r, k, v, lw, u, s0, chunk=chunk, interpret=interpret)


def _rwkv_fwd(r, k, v, lw, u, s0, chunk, interpret):
    return _rwkv(r, k, v, lw, u, s0, chunk, interpret), (r, k, v, lw, u, s0)


def _rwkv_bwd(chunk, interpret, res, g):
    r, k, v, lw, u, s0 = res
    _, vjp = jax.vjp(lambda *a: ref.rwkv_scan_ref(*a), r, k, v, lw, u, s0)
    return vjp(g)


_rwkv.defvjp(_rwkv_fwd, _rwkv_bwd)


def rwkv_scan(r, k, v, lw, u, s0, *, chunk=64, interpret=False):
    """Chunked WKV: r,k,v,lw (B,T,H,hd); u (H,hd); s0 (B,H,hd,hd)."""
    return _rwkv(r, k, v, lw, u, s0, chunk, interpret)


# ------------------------------------------------------------------ lru scan
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _lru(a, b, h0, chunk, interpret):
    return lru_scan_chunked(a, b, h0, chunk=chunk, interpret=interpret)


def _lru_fwd(a, b, h0, chunk, interpret):
    return _lru(a, b, h0, chunk, interpret), (a, b, h0)


def _lru_bwd(chunk, interpret, res, g):
    a, b, h0 = res
    _, vjp = jax.vjp(lambda *x: ref.lru_scan_ref(*x), a, b, h0)
    return vjp(g)


_lru.defvjp(_lru_fwd, _lru_bwd)


def lru_scan(a, b, h0, *, chunk=128, interpret=False):
    """First-order scan h_t = a_t h_{t-1} + b_t.  a,b: (B,T,D); h0: (B,D)."""
    return _lru(a, b, h0, chunk, interpret)


# -------------------------------------------------------------------- matmul
def matmul(a, b, *, bm=256, bn=256, bk=256, interpret=False):
    return matmul_tiled(a, b, bm=bm, bn=bn, bk=bk, interpret=interpret)
