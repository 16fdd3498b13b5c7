"""Per-architecture smoke tests (reduced configs, CPU): one forward + one
train step, shape and finiteness assertions; decode == full-forward exactness;
window ring-buffer correctness; MoE/aux behaviours.  Full configs are only
exercised via the dry-run (ShapeDtypeStruct, no allocation)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import ExecConfig, Model
from repro.optim import AdamW
from repro.train import make_train_step

EC = ExecConfig(rec_chunk=4)

# tiny configs of these archs are still the suite's heaviest (recurrence /
# vision towers / enc-dec); they run in the full lane only
_HEAVY = {"recurrentgemma_2b", "llama_3_2_vision_11b", "seamless_m4t_large_v2"}
ARCH_PARAMS = [
    pytest.param(a, marks=pytest.mark.slow) if a in _HEAVY else a
    for a in configs.ARCH_IDS
]


def make_batch(cfg, B=2, S=12, seed=1, with_labels=False):
    rng = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(rng, (B, S + 1), 0, cfg.vocab_size)
    batch = {"tokens": tokens[:, :-1]}
    if with_labels:
        batch["labels"] = tokens[:, 1:]
    else:
        batch["tokens"] = tokens[:, :S]
    if cfg.is_encdec:
        batch["frames"] = 0.1 * jax.random.normal(rng, (B, cfg.ctx_tokens, cfg.d_model))
    if cfg.family == "vlm":
        batch["ctx_embeds"] = 0.1 * jax.random.normal(rng, (B, cfg.ctx_tokens, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ARCH_PARAMS)
def test_forward_shapes_finite(arch):
    cfg = configs.get_tiny(arch)
    m = Model(cfg, EC)
    params = m.init(jax.random.PRNGKey(0))
    B, S = 2, 12
    h, aux = m.forward(params, make_batch(cfg, B, S))
    assert h.shape == (B, S, cfg.d_model)
    assert bool(jnp.isfinite(h).all())
    logits = m.logits(params, h)
    assert logits.shape == (B, S, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits).all())
    if cfg.ffn == "moe":
        assert bool(jnp.isfinite(aux)) and float(aux) > 0.0


@pytest.mark.parametrize("arch", ARCH_PARAMS)
def test_train_step_decreases_loss(arch):
    cfg = configs.get_tiny(arch)
    m = Model(cfg, EC)
    params = m.init(jax.random.PRNGKey(0))
    opt = AdamW(lr=1e-3)
    ost = opt.init(params)
    batch = make_batch(cfg, B=2, S=12, with_labels=True)
    step = jax.jit(make_train_step(m, opt))
    p, o, met = step(params, ost, batch)
    l0 = float(met["loss"])
    assert np.isfinite(l0)
    for _ in range(8):
        p, o, met = step(p, o, batch)
    assert float(met["loss"]) < l0


@pytest.mark.parametrize("arch", ARCH_PARAMS)
def test_decode_matches_forward(arch):
    """Prefill + decode_step must reproduce the full-forward logits exactly
    (same compute path discipline across all 4 block kinds)."""
    cfg = configs.get_tiny(arch)
    m = Model(cfg, EC)
    params = m.init(jax.random.PRNGKey(0))
    B, S = 2, 12
    batch = make_batch(cfg, B, S)
    tokens = batch["tokens"]
    h, _ = m.forward(params, batch)
    want = m.logits(params, h)[:, -1]
    pb = dict(batch, tokens=tokens[:, : S - 1], max_len=S)
    _, states = m.prefill(params, pb)
    got, _ = m.decode_step(params, tokens[:, S - 1 : S], states, jnp.int32(S - 1))
    np.testing.assert_allclose(np.asarray(want), np.asarray(got), atol=1e-3)


@pytest.mark.slow
def test_multi_step_decode_chain():
    cfg = configs.get_tiny("recurrentgemma_2b")  # covers ring buffer + rglru state
    m = Model(cfg, EC)
    params = m.init(jax.random.PRNGKey(0))
    B, S = 2, 24  # window = 8 << S
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab_size)
    h, _ = m.forward(params, {"tokens": tokens})
    want = m.logits(params, h)[:, -1]
    _, states = m.prefill(params, {"tokens": tokens[:, : S - 4], "max_len": S})
    for i in range(S - 4, S):
        got, states = m.decode_step(params, tokens[:, i : i + 1], states, jnp.int32(i))
    np.testing.assert_allclose(np.asarray(want), np.asarray(got), atol=1e-3)


def test_rwkv_chunked_equals_scan():
    from repro.models.rwkv6 import wkv_chunked, wkv_scan_ref

    rng = jax.random.PRNGKey(0)
    B, T, H, hd = 2, 32, 3, 8
    ks = jax.random.split(rng, 6)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, hd)) for i in range(3))
    lw = -jnp.exp(jax.random.normal(ks[3], (B, T, H, hd)))  # log decay <= 0
    u = jax.random.normal(ks[4], (H, hd))
    s0 = jax.random.normal(ks[5], (B, H, hd, hd))
    y1, s1 = wkv_scan_ref(r, k, v, lw, u, s0)
    for chunk in (4, 8, 16, 32):
        y2, s2 = wkv_chunked(r, k, v, lw, u, s0, chunk=chunk)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4, rtol=1e-4)


def test_rwkv_chunked_stability_strong_decay():
    """Strong decays (w -> 0) must not overflow the chunked form."""
    from repro.models.rwkv6 import wkv_chunked, wkv_scan_ref

    B, T, H, hd = 1, 64, 2, 8
    rng = jax.random.PRNGKey(3)
    ks = jax.random.split(rng, 5)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, hd)) for i in range(3))
    lw = jnp.full((B, T, H, hd), -12.0)  # near-total per-step decay
    u = jax.random.normal(ks[3], (H, hd))
    s0 = jnp.zeros((B, H, hd, hd))
    y1, _ = wkv_scan_ref(r, k, v, lw, u, s0)
    y2, _ = wkv_chunked(r, k, v, lw, u, s0, chunk=16)
    assert bool(jnp.isfinite(y2).all())
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4, rtol=1e-4)


def test_lru_scan_matches_ref():
    from repro.models.rglru import lru_scan, lru_scan_ref

    B, T, D = 3, 40, 16
    rng = jax.random.PRNGKey(1)
    a = jax.nn.sigmoid(jax.random.normal(rng, (B, T, D)))
    b = jax.random.normal(jax.random.fold_in(rng, 1), (B, T, D))
    h0 = jax.random.normal(jax.random.fold_in(rng, 2), (B, D))
    y1, h1 = lru_scan_ref(a, b, h0)
    y2, h2 = lru_scan(a, b, h0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-5, rtol=1e-5)


def test_moe_capacity_drops_tokens():
    """Above-capacity tokens are dropped (train regime) but never in the
    decode regime (drop-free small-T path)."""
    cfg = configs.get_tiny("arctic_480b")
    from repro.models.moe import moe_apply, moe_init

    p = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model), jnp.float32)
    y, aux = moe_apply(cfg, p, x)
    assert y.shape == x.shape and bool(jnp.isfinite(y).all())


def test_param_count_sane():
    # spot check: llama3-405b analytic count is ~405B (±10%)
    cfg = configs.get("llama3_405b")
    n = cfg.param_count()
    assert 3.6e11 < n < 4.6e11, n
    # MoE active < total
    moe = configs.get("arctic_480b")
    assert moe.active_param_count() < moe.param_count()
    assert 3.9e11 < moe.param_count() < 5.6e11, moe.param_count()


def test_vocab_padding():
    cfg = configs.get("seamless_m4t_large_v2")
    assert cfg.padded_vocab % 256 == 0 and cfg.padded_vocab >= cfg.vocab_size


def test_depth_cut_keeps_published_widths():
    full = configs.get("qwen2_7b")
    cut = configs.get("qwen2_7b", n_layers=8)
    assert (cut.n_layers, cut.n_groups) == (8, 8)
    for f in ("d_model", "n_heads", "n_kv_heads", "d_head", "d_ff", "vocab_size", "group"):
        assert getattr(cut, f) == getattr(full, f), f
    assert configs.get("qwen2_7b") is full


@pytest.mark.parametrize(
    "S,max_len,window,heads",
    [
        (40, 48, 0, {}),  # the ring holds the prompt
        (40, 24, 0, {}),  # a prompt longer than the ring (Sq > W)
        (37, 24, 0, {}),  # an odd prompt length, longer than the ring
        (40, 48, 8, {}),  # local attention: keeps the XLA path on a TPU too
        (40, 48, 0, {"d_model": 256, "n_heads": 2, "n_kv_heads": 1, "d_head": 128}),  # 128-wide heads
        # the served configuration's grouping: 28 query heads over 4 KV heads (7:1)
        (40, 48, 0, {"d_model": 448, "n_heads": 28, "n_kv_heads": 4, "d_head": 16}),
    ],
)
def test_prefill_flash_matches_xla(S, max_len, window, heads, monkeypatch):
    """A cached prefill through the flash kernel (interpret mode, as a
    program built for a TPU would take it) gives the XLA path's last hidden
    state, first token and cache; the lowering counters say which path
    each took."""
    from repro import obs
    from repro.kernels import ops as kops
    from repro.models import attention

    takes = attention._prefill_takes_flash

    def takes_on_tpu(causal, window):
        with kops.targeting("tpu"):
            return takes(causal, window)

    cfg = dataclasses.replace(configs.get_tiny("qwen2_7b"), window=window, **heads)
    params = Model(cfg, EC).init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, S), 0, cfg.vocab_size)
    out, lowered = {}, {}
    for tpu in (False, True):
        if tpu:
            monkeypatch.setattr(attention, "_prefill_takes_flash", takes_on_tpu)
        m = Model(cfg, EC)  # a fresh model and function: each loop traces anew

        def prefill(params, tokens, m=m):
            hidden, states = m.prefill(params, {"tokens": tokens, "max_len": max_len})
            first = jnp.argmax(m.logits(params, hidden[:, None])[:, 0], -1)
            return hidden, first, states

        before = {p: obs.counter(f"attn.prefill_lowered.{p}").value for p in ("flash", "xla")}
        out[tpu] = jax.jit(prefill)(params, tokens)
        lowered[tpu] = {p: obs.counter(f"attn.prefill_lowered.{p}").value - n
                        for p, n in before.items()}
    assert lowered[False] == {"flash": 0, "xla": 1}  # one scan body, traced once
    assert lowered[True] == ({"flash": 1, "xla": 0} if window == 0 else lowered[False])
    (h_x, tok_x, st_x), (h_f, tok_f, st_f) = out[False], out[True]
    np.testing.assert_allclose(np.asarray(h_f, np.float32), np.asarray(h_x, np.float32),
                               atol=2e-2, rtol=2e-2)
    np.testing.assert_array_equal(np.asarray(tok_f), np.asarray(tok_x))
    kv_x, kv_f = st_x[0]["pos0"]["kv"], st_f[0]["pos0"]["kv"]
    assert kv_f["k"].shape[-2] == min(max_len, window or max_len)
    np.testing.assert_array_equal(np.asarray(kv_f["pos"]), np.asarray(kv_x["pos"]))
    for name in ("k", "v"):
        np.testing.assert_allclose(np.asarray(kv_f[name], np.float32),
                                   np.asarray(kv_x[name], np.float32), atol=2e-2, rtol=2e-2)


def test_prefill_path_follows_platform_window_and_sharding():
    """The kernel serves causal whole-prompt attention in a program built
    for a TPU (the default backend, or one a ``targeting`` block names); a
    window, the CPU backend and a program sharded over several devices keep
    the XLA path."""
    import types

    from repro.kernels import ops as kops
    from repro.models import attention
    from repro.parallel.api import ShardingRules, sharding_context

    takes = attention._prefill_takes_flash
    assert not takes(True, 0)  # the CPU backend
    assert kops.interpret_mode()
    with kops.targeting("tpu"):  # a described chip, compiled from this host
        assert takes(True, 0) and not kops.interpret_mode()
        assert not takes(True, 8)
        assert not takes(False, 0)
        with sharding_context(types.SimpleNamespace(size=4), ShardingRules()):
            assert not takes(True, 0)
        with sharding_context(types.SimpleNamespace(size=1), ShardingRules()):
            assert takes(True, 0)
