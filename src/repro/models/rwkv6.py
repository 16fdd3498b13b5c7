"""RWKV-6 "Finch" block: data-dependent-decay linear recurrence (arXiv:2404.05892).

Per head with state S ∈ R^{hd×hd}, per-channel data-dependent decay w_t∈(0,1):

    y_t = r_t · (S_{t-1} + diag(u) k_t v_tᵀ)
    S_t = diag(w_t) S_{t-1} + k_t v_tᵀ

Sequence processing uses an **exact, numerically stable chunked** form (scan
over chunks of length L = ExecConfig.rec_chunk, matmuls within):
all exponentials are of non-positive arguments (cumulative-decay differences
with s ≤ t and chunk-end references), so nothing overflows — no decay clamp
is needed.  The Pallas kernel (kernels/rwkv_scan.py) implements the same
algorithm with VMEM-resident state; ``ref.py``-style exactness is provided by
:func:`wkv_scan_ref` (naive per-token scan), which is also the decode path.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ExecConfig, ModelConfig
from .layers import _nrm, norm_apply

__all__ = ["rwkv_init", "rwkv_apply", "init_rwkv_state", "wkv_scan_ref", "wkv_chunked"]

_LORA_W = 64  # decay LoRA rank (rwkv6 default for 7B)
_LORA_MIX = 32  # ddlerp LoRA rank


def rwkv_init(rng, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    H, hd = cfg.n_heads, cfg.d_head
    ks = jax.random.split(rng, 12)
    s = 1.0 / np.sqrt(d)
    return {
        "ln1": {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)},
        "ln2": {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)},
        "tm": {
            "mu_x": jnp.zeros((d,), jnp.float32),
            "mu_5": jnp.zeros((5, d), jnp.float32),  # w,k,v,r,g ddlerp biases
            "mix_w1": _nrm(ks[0], (d, 5 * _LORA_MIX), s),
            "mix_w2": _nrm(ks[1], (5, _LORA_MIX, d), 0.02),
            "w0": jnp.full((d,), -1.0, jnp.float32),  # decay bias (log-log space)
            "w1": _nrm(ks[2], (d, _LORA_W), s),
            "w2": _nrm(ks[3], (_LORA_W, d), 0.02),
            "u": _nrm(ks[4], (H, hd), 0.5),  # bonus ("time_faaaa")
            "wr": _nrm(ks[5], (d, d), s),
            "wk": _nrm(ks[6], (d, d), s),
            "wv": _nrm(ks[7], (d, d), s),
            "wg": _nrm(ks[8], (d, d), s),
            "wo": _nrm(ks[9], (d, d), s),
            "ln_x": {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)},
        },
        "cm": {
            "mu_k": jnp.zeros((d,), jnp.float32),
            "mu_r": jnp.zeros((d,), jnp.float32),
            "wk": _nrm(ks[10], (d, cfg.d_ff), s),
            "wv": _nrm(ks[11], (cfg.d_ff, d), 1.0 / np.sqrt(cfg.d_ff)),
            "wr": _nrm(ks[10], (d, d), s),
        },
    }


def init_rwkv_state(cfg: ModelConfig, batch: int) -> dict:
    H, hd = cfg.n_heads, cfg.d_head
    return {
        "shift_tm": jnp.zeros((batch, cfg.d_model), jnp.dtype(cfg.compute_dtype)),
        "shift_cm": jnp.zeros((batch, cfg.d_model), jnp.dtype(cfg.compute_dtype)),
        "wkv": jnp.zeros((batch, H, hd, hd), jnp.float32),
    }


# ----------------------------------------------------------------- recurrence
def wkv_scan_ref(r, k, v, lw, u, s0):
    """Exact per-token scan (oracle + decode path).

    r,k,v,lw: (B,T,H,hd)   lw = log decay (<= 0)
    u: (H,hd)   s0: (B,H,hd,hd)  ->  y: (B,T,H,hd), sT: (B,H,hd,hd)
    """
    rf, kf, vf, lwf = (a.astype(jnp.float32) for a in (r, k, v, lw))

    def step(S, inp):
        rt, kt, vt, lwt = inp  # (B,H,hd)
        akv = kt[..., :, None] * vt[..., None, :]  # (B,H,hd,hd)
        yt = jnp.einsum("bhk,bhkv->bhv", rt, S + u[None, :, :, None] * akv)
        S = jnp.exp(lwt)[..., :, None] * S + akv
        return S, yt

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (rf, kf, vf, lwf))
    sT, ys = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(r.dtype), sT


def wkv_chunked(r, k, v, lw, u, s0, chunk: int = 32, unroll: bool = False):
    """Exact chunked form: scan over T/L chunks, matmuls within a chunk.

    Stability: with c = within-chunk cumsum of lw (c <= 0, decreasing),
      inter:  y += (r_t ⊙ e^{c_{t-1}}) · S_chunk          (exponent <= 0)
      intra:  score_{ts} = Σ_i r_t k_s e^{c_{t-1}-c_s}, s<t  (exponent <= 0)
      state:  S' = e^{c_L} ⊙ S + Σ_s (k_s e^{c_L - c_s}) v_sᵀ (exponent <= 0)
    """
    B, T, H, hd = r.shape
    L = min(chunk, T)
    if T % L:
        raise ValueError(f"T={T} not divisible by rec_chunk={L}")
    nc = T // L
    rf, kf, vf, lwf = (
        a.astype(jnp.float32).reshape(B, nc, L, H, hd).transpose(1, 0, 3, 2, 4)
        for a in (r, k, v, lw)
    )  # (nc, B, H, L, hd)

    c = jnp.cumsum(lwf, axis=-2)  # (nc,B,H,L,hd)
    q_dec = rf * jnp.exp(c - lwf)  # r_t e^{c_{t-1}}
    k_end = kf * jnp.exp(c[..., -1:, :] - c)  # k_s e^{c_L - c_s}
    # intra-chunk pairwise scores (exact log-space differences, s<t)
    expo = (c - lwf)[..., :, None, :] - c[..., None, :, :]  # (nc,B,H,L,L,hd)
    tri = jnp.tril(jnp.ones((L, L), bool), k=-1)[None, None, None]
    ew = jnp.where(tri[..., None], jnp.exp(jnp.minimum(expo, 0.0)), 0.0)
    scores = jnp.einsum("nbhtsi,nbhti,nbhsi->nbhts", ew, rf, kf)
    diag = jnp.einsum("nbhti,hi,nbhti->nbht", rf, u.astype(jnp.float32), kf)
    ii = jnp.arange(L)
    scores = scores.at[..., ii, ii].add(diag)
    y_intra = jnp.einsum("nbhts,nbhsv->nbhtv", scores, vf)

    def body(S, xs):
        q_dec_c, k_end_c, v_c, y_in_c, c_last = xs
        y = y_in_c + jnp.einsum("bhti,bhiv->bhtv", q_dec_c, S)
        S = jnp.exp(c_last)[..., None] * S + jnp.einsum("bhsi,bhsv->bhiv", k_end_c, v_c)
        return S, y

    xs = (q_dec, k_end, vf, y_intra, c[..., -1, :])
    if unroll:
        # python loop over chunks: exact cost_analysis (no while-loop body
        # undercounting) — used by the dry-run
        S, ys_l = s0, []
        for n in range(nc):
            S, yn = body(S, jax.tree.map(lambda a: a[n], xs))
            ys_l.append(yn)
        sT, ys = S, jnp.stack(ys_l, axis=0)
    else:
        sT, ys = jax.lax.scan(body, s0, xs)
    y = ys.transpose(1, 0, 3, 2, 4).reshape(B, T, H, hd)
    return y.astype(r.dtype), sT


# ----------------------------------------------------------------- the block
def _ddlerp(x, x_prev, tm):
    """Data-dependent token-shift interpolation (Finch §3)."""
    dx = x_prev - x
    xxx = x + dx * tm["mu_x"].astype(x.dtype)
    z = jnp.tanh(xxx @ tm["mix_w1"].astype(x.dtype))  # (B,T,5*R)
    B, T = x.shape[:2]
    z = z.reshape(B, T, 5, _LORA_MIX)
    deltas = jnp.einsum("btfr,frd->btfd", z, tm["mix_w2"].astype(x.dtype))
    mixed = x[:, :, None, :] + dx[:, :, None, :] * (
        tm["mu_5"].astype(x.dtype)[None, None] + deltas
    )
    return tuple(mixed[:, :, i] for i in range(5))  # xw, xk, xv, xr, xg


def rwkv_apply(
    cfg: ModelConfig,
    p: dict,
    x: jnp.ndarray,
    state: dict,
    *,
    exec_cfg: ExecConfig = ExecConfig(),
) -> Tuple[jnp.ndarray, dict]:
    """Full block: time-mix (+residual) then channel-mix (+residual).
    x: (B,T,D).  ``state`` carries shift tokens + wkv state across calls
    (T=1 decode works through the same code path via the ref scan)."""
    B, T, D = x.shape
    H, hd = cfg.n_heads, cfg.d_head
    dt = x.dtype
    tm, cm = p["tm"], p["cm"]

    # ---- time mix (pre-LN stream carries the token shift) -------------------
    xn = norm_apply("layernorm", p["ln1"], x)
    xs = jnp.concatenate([state["shift_tm"][:, None, :], xn[:, :-1]], axis=1)
    xw, xk, xv, xr, xg = _ddlerp(xn, xs, tm)
    r = (xr @ tm["wr"].astype(dt)).reshape(B, T, H, hd)
    k = (xk @ tm["wk"].astype(dt)).reshape(B, T, H, hd)
    v = (xv @ tm["wv"].astype(dt)).reshape(B, T, H, hd)
    g = xg @ tm["wg"].astype(dt)
    wlog = tm["w0"].astype(jnp.float32) + jnp.tanh(
        xw.astype(jnp.float32) @ tm["w1"].astype(jnp.float32)
    ) @ tm["w2"].astype(jnp.float32)
    lw = -jnp.exp(wlog).reshape(B, T, H, hd)  # log decay <= 0

    if T == 1 or exec_cfg.rec_chunk <= 1 or T % min(exec_cfg.rec_chunk, T):
        y, sT = wkv_scan_ref(r, k, v, lw, tm["u"], state["wkv"])
    else:
        y, sT = wkv_chunked(
            r, k, v, lw, tm["u"], state["wkv"],
            chunk=exec_cfg.rec_chunk, unroll=exec_cfg.rec_unroll,
        )

    # per-head groupnorm, gate, out-proj
    yf = y.reshape(B, T, H, hd)
    mu = jnp.mean(yf, axis=-1, keepdims=True)
    var = jnp.var(yf, axis=-1, keepdims=True)
    yn = ((yf - mu) * jax.lax.rsqrt(var + 64e-5)).reshape(B, T, D)
    yn = yn * tm["ln_x"]["scale"].astype(dt) + tm["ln_x"]["bias"].astype(dt)
    out_tm = (yn * jax.nn.silu(g)) @ tm["wo"].astype(dt)
    x = x + out_tm
    new_state = {"shift_tm": xn[:, -1], "wkv": sT}

    # ---- channel mix (its own pre-LN stream) ---------------------------------
    xn2 = norm_apply("layernorm", p["ln2"], x)
    xs2 = jnp.concatenate([state["shift_cm"][:, None, :], xn2[:, :-1]], axis=1)
    dx2 = xs2 - xn2
    xk2 = xn2 + dx2 * cm["mu_k"].astype(dt)
    xr2 = xn2 + dx2 * cm["mu_r"].astype(dt)
    kk = jnp.square(jax.nn.relu(xk2 @ cm["wk"].astype(dt)))
    out_cm = jax.nn.sigmoid(xr2 @ cm["wr"].astype(dt)) * (kk @ cm["wv"].astype(dt))
    y_out = x + out_cm
    new_state["shift_cm"] = xn2[:, -1]
    return y_out, new_state
