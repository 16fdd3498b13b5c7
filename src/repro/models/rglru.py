"""RG-LRU recurrent block (Griffin / RecurrentGemma, arXiv:2402.19427).

Recurrence (elementwise, per channel):

    r_t = sigmoid(W_a x_t + b_a)            # recurrence gate
    i_t = sigmoid(W_x x_t + b_x)            # input gate
    a_t = exp(c * r_t * log(sigmoid(Λ)))    # data-dependent decay, c = 8
    h_t = a_t h_{t-1} + sqrt(1 - a_t²) (i_t ⊙ x_t)

The block (Griffin "recurrent block"): two branches from the pre-norm input —
(a) linear→GeLU and (b) linear→causal depthwise conv1d(width 4)→RG-LRU —
merged multiplicatively and projected back.  Sequence processing uses
``jax.lax.associative_scan`` (O(log T) depth; exact); decode is a single
elementwise step.  The chunked Pallas kernel implements the same first-order
scan with VMEM-resident carry.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .layers import _nrm

__all__ = ["rglru_init", "rglru_apply", "init_rglru_state", "lru_scan_ref", "lru_scan"]

_C = 8.0  # Griffin's fixed decay sharpness


def rglru_init(rng, cfg: ModelConfig) -> dict:
    d, dr = cfg.d_model, cfg.rnn_width
    ks = jax.random.split(rng, 6)
    s = 1.0 / np.sqrt(d)
    # Λ init so that a ∈ [0.9, 0.999] at r=1 (Griffin appendix)
    u = jax.random.uniform(ks[0], (dr,), jnp.float32, 0.9**2, 0.999**2)
    lam = jnp.log(jnp.sqrt(u) / (1.0 - jnp.sqrt(u)))  # logit of a^(1/c)... see note
    return {
        "wx_gelu": _nrm(ks[1], (d, dr), s),  # branch (a)
        "wx_rec": _nrm(ks[2], (d, dr), s),  # branch (b)
        "conv_w": _nrm(ks[3], (cfg.conv_width, dr), 0.1),
        "conv_b": jnp.zeros((dr,), jnp.float32),
        "wa": _nrm(ks[4], (dr, dr), 1.0 / np.sqrt(dr)),
        "ba": jnp.zeros((dr,), jnp.float32),
        "wi": _nrm(ks[5], (dr, dr), 1.0 / np.sqrt(dr)),
        "bi": jnp.zeros((dr,), jnp.float32),
        "lam": lam,
        "wo": _nrm(ks[0], (dr, d), 1.0 / np.sqrt(dr)),
    }


def init_rglru_state(cfg: ModelConfig, batch: int) -> dict:
    dr = cfg.rnn_width
    return {
        "h": jnp.zeros((batch, dr), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_width - 1, dr), jnp.dtype(cfg.compute_dtype)),
    }


# ----------------------------------------------------------------- recurrence
def lru_scan_ref(a, b, h0):
    """Exact per-token scan: h_t = a_t h_{t-1} + b_t.  a,b: (B,T,D)."""
    def step(h, ab):
        at, bt = ab
        h = at * h + bt
        return h, h

    xs = (jnp.moveaxis(a, 1, 0), jnp.moveaxis(b, 1, 0))
    hT, hs = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(hs, 0, 1), hT


def lru_scan(a, b, h0):
    """associative_scan form of the same first-order recurrence (train path).
    Fold h0 into the first step: b_0' = a_0 h0 + b_0."""
    b = b.at[:, 0].add(a[:, 0] * h0)

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2

    aa, hs = jax.lax.associative_scan(combine, (a, b), axis=1)
    return hs, hs[:, -1]


# ----------------------------------------------------------------- the block
def _causal_conv1d(x, w, b, state):
    """Depthwise causal conv. x: (B,T,D), w: (W,D); state: (B,W-1,D) history."""
    W = w.shape[0]
    xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)  # (B, T+W-1, D)
    y = sum(xp[:, i : i + x.shape[1]] * w[i].astype(x.dtype) for i in range(W))
    return y + b.astype(x.dtype), xp[:, -(W - 1) :]


def rglru_apply(
    cfg: ModelConfig,
    p: dict,
    x: jnp.ndarray,
    state: dict,
) -> Tuple[jnp.ndarray, dict]:
    """Temporal-mix half of the Griffin block (residual handled by caller).
    x: (B,T,D) pre-normed. Returns (out (B,T,D), new_state)."""
    dt = x.dtype
    ga = jax.nn.gelu(x @ p["wx_gelu"].astype(dt))  # branch (a)
    xb = x @ p["wx_rec"].astype(dt)  # branch (b)
    xb, new_conv = _causal_conv1d(xb, p["conv_w"], p["conv_b"], state["conv"])

    # RG-LRU gates (fp32 for the recurrence)
    xf = xb.astype(jnp.float32)
    r = jax.nn.sigmoid(xf @ p["wa"].astype(jnp.float32) + p["ba"])
    i = jax.nn.sigmoid(xf @ p["wi"].astype(jnp.float32) + p["bi"])
    log_a_base = -jax.nn.softplus(-p["lam"])  # log sigmoid(Λ)  (<= 0)
    log_a = _C * r * log_a_base[None, None, :]
    a = jnp.exp(log_a)
    gated = i * xf
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * gated

    if x.shape[1] == 1:
        h = a[:, 0] * state["h"] + b[:, 0]
        hs, hT = h[:, None], h
    else:
        hs, hT = lru_scan(a, b, state["h"])

    out = (hs.astype(dt) * ga) @ p["wo"].astype(dt)
    return out, {"h": hT, "conv": new_conv}
