"""Pallas kernel validation: interpret=True vs ref.py oracles, shape/dtype
sweeps (per-kernel allclose contract) + hypothesis property sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import flash_attention, ops, ref

TOL = {jnp.float32: 2e-4, jnp.bfloat16: 2e-2}


def _tol(dt):
    return TOL[jnp.bfloat16 if dt == jnp.bfloat16 else jnp.float32]


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize(
    "B,H,Kh,Sq,hd,bq,bkv",
    [
        (1, 2, 2, 32, 16, 16, 16),  # MHA
        (2, 4, 2, 64, 32, 32, 16),  # GQA g=2
        (1, 8, 1, 64, 16, 16, 64),  # MQA
        (1, 2, 1, 128, 64, 64, 32),
        (1, 14, 2, 64, 32, 16, 32),  # GQA g=7, the grouping of Qwen2-7B's 28/4
        (2, 14, 2, 50, 32, 16, 16),  # length not a tile multiple, B > 1
        (2, 4, 2, 100, 16, 32, 64),  # padded in both tiles, bkv > bq
        (2, 6, 2, 72, 128, 32, 16),  # heads one lane tile (128) wide
    ],
)
def test_flash_attention_sweep(dtype, causal, B, H, Kh, Sq, hd, bq, bkv):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, Kh, Sq, hd), dtype)
    v = jax.random.normal(ks[2], (B, Kh, Sq, hd), dtype)
    o = ops.flash_attention(q, k, v, causal=causal, block_q=bq, block_kv=bkv, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(want, np.float32), atol=_tol(dtype), rtol=_tol(dtype)
    )


@settings(max_examples=8, deadline=None)
@given(
    logsq=st.integers(5, 7),
    bq=st.sampled_from([16, 32, 64]),
    bkv=st.sampled_from([16, 32, 64]),
    seed=st.integers(0, 100),
)
def test_flash_attention_property(logsq, bq, bkv, seed):
    """Block shape must never change the result (tuning-safety property)."""
    Sq = 2**logsq
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, Sq, 2, 16))
    k = jax.random.normal(ks[1], (1, 2, Sq, 16))
    v = jax.random.normal(ks[2], (1, 2, Sq, 16))
    o = ops.flash_attention(q, k, v, causal=True, block_q=bq, block_kv=bkv, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("bq,bkv", [(16, 16), (16, 64), (64, 16), (32, 32)])
def test_flash_attention_skips_future_tiles(bq, bkv):
    """Under causal attention a KV tile entirely in a query tile's future
    repeats the block index of the step before it, so the pipeline fetches
    nothing new for it; every tile the query tile needs is visited in order."""
    n_q, n_kv = 128 // bq, 128 // bkv
    for iq in range(n_q):
        idx = [int(flash_attention.kv_block_index(iq, ikv, block_q=bq, block_kv=bkv,
                                                  causal=True)) for ikv in range(n_kv)]
        last = ((iq + 1) * bq - 1) // bkv
        assert idx[: last + 1] == list(range(last + 1))
        for ikv in range(last + 1, n_kv):  # fully-future tiles
            assert ikv * bkv > (iq + 1) * bq - 1
            assert idx[ikv] == idx[ikv - 1]
        assert [int(flash_attention.kv_block_index(iq, ikv, block_q=bq, block_kv=bkv,
                                                   causal=False))
                for ikv in range(n_kv)] == list(range(n_kv))


def test_flash_attention_grad_matches_ref():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 32, 2, 16))
    k = jax.random.normal(ks[1], (1, 2, 32, 16))
    v = jax.random.normal(ks[2], (1, 2, 32, 16))

    def f(q, k, v):
        return jnp.sum(ops.flash_attention(q, k, v, interpret=True, block_q=16, block_kv=16) ** 2)

    def fr(q, k, v):
        return jnp.sum(ref.flash_attention_ref(q, k, v) ** 2)

    g = jax.grad(f, (0, 1, 2))(q, k, v)
    gr = jax.grad(fr, (0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


# ------------------------------------------------------------ decode attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bkv", [16, 64, 128])
@pytest.mark.parametrize("length", [1, 37, 128])
def test_decode_attention_sweep(dtype, bkv, length):
    B, H, Kh, S, hd = 2, 4, 2, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, Kh, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, Kh, S, hd), dtype)
    valid = (jnp.arange(S) < length).astype(jnp.int32)[None].repeat(B, 0)
    o = ops.decode_attention(q, k, v, valid, block_kv=bkv, interpret=True)
    want = ref.decode_attention_ref(q, k, v, length)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(want, np.float32), atol=_tol(dtype), rtol=_tol(dtype)
    )


def test_decode_attention_ring_validity():
    """Scattered validity (ring buffers) must be honoured, not just prefixes."""
    B, H, Kh, S, hd = 1, 2, 1, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, Kh, S, hd))
    v = jax.random.normal(ks[2], (B, Kh, S, hd))
    valid = jax.random.bernoulli(ks[3], 0.5, (B, S)).astype(jnp.int32)
    o = ops.decode_attention(q, k, v, valid, block_kv=16, interpret=True)
    # dense oracle with the same mask
    s = jnp.einsum("bkgh,bksh->bkgs", q.reshape(B, Kh, 2, hd), k) / np.sqrt(hd)
    s = jnp.where(valid[:, None, None] > 0, s, -1e30)
    want = jnp.einsum("bkgs,bksh->bkgh", jax.nn.softmax(s, -1), v).reshape(B, H, hd)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want), atol=1e-4)


# ------------------------------------------------------------------ rwkv scan
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("chunk", [8, 16, 32])
@pytest.mark.parametrize("B,T,H,hd", [(1, 32, 2, 8), (2, 64, 2, 16), (1, 64, 1, 32)])
def test_rwkv_scan_sweep(dtype, chunk, B, T, H, hd):
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, hd), dtype) for i in range(3))
    lw = -jnp.exp(jax.random.normal(ks[3], (B, T, H, hd))).astype(dtype)
    u = jax.random.normal(ks[4], (H, hd), dtype)
    s0 = jax.random.normal(ks[5], (B, H, hd, hd), jnp.float32)
    y, sT = ops.rwkv_scan(r, k, v, lw, u, s0, chunk=chunk, interpret=True)
    yw, sw = ref.rwkv_scan_ref(r, k, v, lw, u, s0)
    tol = 5e-2 if dtype == jnp.bfloat16 else 5e-4
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(yw, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sw), atol=tol, rtol=tol)


def test_rwkv_scan_strong_decay_stable():
    B, T, H, hd = 1, 32, 1, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, hd)) for i in range(3))
    lw = jnp.full((B, T, H, hd), -14.0)
    u = jax.random.normal(ks[3], (H, hd))
    s0 = jnp.zeros((B, H, hd, hd))
    y, _ = ops.rwkv_scan(r, k, v, lw, u, s0, chunk=16, interpret=True)
    yw, _ = ref.rwkv_scan_ref(r, k, v, lw, u, s0)
    assert bool(jnp.isfinite(y).all())
    np.testing.assert_allclose(np.asarray(y), np.asarray(yw), atol=1e-4)


# ------------------------------------------------------------------- lru scan
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("chunk,block_d", [(16, 16), (32, 32), (64, 16)])
def test_lru_scan_sweep(dtype, chunk, block_d):
    B, T, D = 2, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, T, D))).astype(dtype)
    b = jax.random.normal(ks[1], (B, T, D), dtype)
    h0 = jax.random.normal(ks[2], (B, D), jnp.float32)
    hs, hT = ops.lru_scan(a, b, h0, chunk=chunk, interpret=True)
    hw, hTw = ref.lru_scan_ref(a, b, h0)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(hs, np.float32), np.asarray(hw, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hTw), atol=tol, rtol=tol)


# --------------------------------------------------------------------- matmul
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bm,bn,bk", [(32, 32, 32), (64, 32, 96), (128, 64, 48)])
def test_matmul_sweep(dtype, bm, bn, bk):
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    a = jax.random.normal(ks[0], (128, 96), dtype)
    b = jax.random.normal(ks[1], (96, 64), dtype)
    o = ops.matmul(a, b, bm=bm, bn=bn, bk=bk, interpret=True)
    want = ref.matmul_ref(a, b)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(want, np.float32), atol=_tol(dtype), rtol=_tol(dtype)
    )


@settings(max_examples=10, deadline=None)
@given(
    bm=st.sampled_from([16, 32, 64]),
    bn=st.sampled_from([16, 32, 64]),
    bk=st.sampled_from([16, 32, 64]),
    seed=st.integers(0, 50),
)
def test_matmul_property_tile_invariance(bm, bn, bk, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    a = jax.random.normal(ks[0], (64, 64))
    b = jax.random.normal(ks[1], (64, 64))
    o = ops.matmul(a, b, bm=bm, bn=bn, bk=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref.matmul_ref(a, b)), atol=1e-4)
