"""Shared layers: norms, RoPE, FFNs, embeddings — pure JAX, init + apply."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "norm_init",
    "norm_apply",
    "linear_init",
    "linear_apply",
    "rope_apply",
    "ffn_init",
    "ffn_apply",
    "embed_init",
]


def _nrm(rng, shape, scale):
    return scale * jax.random.truncated_normal(rng, -2.0, 2.0, shape, jnp.float32)


# ------------------------------------------------------------------- norms
def norm_init(kind: str, d: int) -> dict:
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


@jax.named_scope("norm")
def norm_apply(kind: str, p: dict, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        y = x * jax.lax.rsqrt(ms + eps) * p["scale"]
    return y.astype(dt)


# ------------------------------------------------------------------ linear
def linear_init(rng, d_in: int, d_out: int, *, bias: bool = False, scale: float = None) -> dict:
    scale = scale if scale is not None else (1.0 / np.sqrt(d_in))
    p = {"w": _nrm(rng, (d_in, d_out), scale)}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def linear_apply(p: dict, x: jnp.ndarray, dtype=None) -> jnp.ndarray:
    dtype = dtype or x.dtype
    y = x @ p["w"].astype(dtype)
    if "b" in p:
        y = y + p["b"].astype(dtype)
    return y


# -------------------------------------------------------------------- RoPE
def rope_apply(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: (S,) int32.  On-the-fly cos/sin (no
    table — needed for 500k-position decode)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]  # (S, half)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    dt = x.dtype
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1
    ).astype(dt)


# --------------------------------------------------------------------- FFN
def ffn_init(rng, kind: str, d: int, d_ff: int) -> dict:
    k1, k2, k3 = jax.random.split(rng, 3)
    if kind in ("swiglu", "geglu"):
        return {
            "wi": _nrm(k1, (d, d_ff), 1.0 / np.sqrt(d)),
            "wg": _nrm(k2, (d, d_ff), 1.0 / np.sqrt(d)),
            "wo": _nrm(k3, (d_ff, d), 1.0 / np.sqrt(d_ff)),
        }
    if kind == "gelu":
        return {
            "wi": _nrm(k1, (d, d_ff), 1.0 / np.sqrt(d)),
            "bi": jnp.zeros((d_ff,), jnp.float32),
            "wo": _nrm(k3, (d_ff, d), 1.0 / np.sqrt(d_ff)),
            "bo": jnp.zeros((d,), jnp.float32),
        }
    raise ValueError(f"unknown ffn kind {kind}")


def ffn_apply(kind: str, p: dict, x: jnp.ndarray) -> jnp.ndarray:
    dt = x.dtype
    if kind in ("swiglu", "geglu"):
        act = jax.nn.silu if kind == "swiglu" else jax.nn.gelu
        h = act(x @ p["wg"].astype(dt)) * (x @ p["wi"].astype(dt))
        return h @ p["wo"].astype(dt)
    if kind == "gelu":
        h = jax.nn.gelu(x @ p["wi"].astype(dt) + p["bi"].astype(dt))
        return h @ p["wo"].astype(dt) + p["bo"].astype(dt)
    raise ValueError(f"unknown ffn kind {kind}")


# --------------------------------------------------------------- embedding
def embed_init(rng, vocab: int, d: int) -> dict:
    return {"table": _nrm(rng, (vocab, d), 1.0)}
