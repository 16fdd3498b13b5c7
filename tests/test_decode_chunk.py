"""``serve.decode_chunk``: a chunk of k steps, which updates the state in
place, serves what k chunks of one step serve, for every block kind (tiny
configs, CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, obs
from repro.launch import serve
from repro.models import ExecConfig, Model

EC = ExecConfig(rec_chunk=4)
K = 8

#: block kind -> a tiny config that holds it
KINDS = {
    "attn": "qwen2_7b",
    "moe": "arctic_480b",
    "local_window_rglru": "recurrentgemma_2b",  # ring of 8 slots wraps below
    "cross_vlm": "llama_3_2_vision_11b",
    "cross_encdec": "seamless_m4t_large_v2",
    "rwkv": "rwkv6_7b",
}


def _prefilled(arch, B=2, P=6):
    cfg = configs.get_tiny(arch)
    model = Model(cfg, EC)
    params = model.init(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(rng, (B, P), 0, cfg.vocab_size), "max_len": P + K}
    if cfg.is_encdec:
        batch["frames"] = 0.1 * jax.random.normal(rng, (B, cfg.ctx_tokens, cfg.d_model))
    if cfg.family == "vlm":
        batch["ctx_embeds"] = 0.1 * jax.random.normal(rng, (B, cfg.ctx_tokens, cfg.d_model))
    hidden, states = model.prefill(params, batch)
    token = jnp.argmax(model.logits(params, hidden[:, None])[:, 0], -1).astype(jnp.int32)[:, None]
    return model, params, batch, token, states, jnp.int32(P)


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_chunk_matches_single_steps(kind):
    model, params, _, token, states, pos = _prefilled(KINDS[kind])
    if kind == "local_window_rglru":
        assert 0 < model.cfg.window < int(pos) + K  # the decode wraps the ring
    t8, s8, p8, toks8 = serve.decode_chunk(model, K)(params, token, states, pos)
    one = serve.decode_chunk(model, 1)
    toks = []
    for _ in range(K):
        token, states, pos, tk = one(params, token, states, pos)
        toks.append(tk)
    np.testing.assert_array_equal(np.asarray(toks8), np.concatenate(toks))
    np.testing.assert_array_equal(np.asarray(t8), np.asarray(token))
    assert int(p8) == int(pos)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
                 s8, states)


def test_chunk_tokens_match_forward():
    """Each greedy token of a k=8 chunk is the argmax of the full forward
    over the prompt and the tokens before it."""
    model, params, batch, token, states, pos = _prefilled("qwen2_7b")
    _, _, _, toks = serve.decode_chunk(model, K)(params, token, states, pos)
    seq = jnp.concatenate([batch["tokens"], token, toks[:-1, :, 0].T], axis=1)
    h, _ = model.forward(params, {"tokens": seq})
    want = jnp.argmax(model.logits(params, h)[:, int(pos):], -1)
    np.testing.assert_array_equal(np.asarray(toks[:, :, 0].T), np.asarray(want))


def test_run_chunk_counts_fresh_and_in_place_steps():
    """A lone step writes a fresh state buffer; every step of a longer
    chunk runs in place."""
    model, params, _, token, states, pos = _prefilled("qwen2_7b")
    fresh = obs.counter("serve.decode_steps.fresh")
    in_place = obs.counter("serve.decode_steps.in_place")
    f0, i0 = fresh.value, in_place.value
    for k in (K, 1, 2):
        token, states, pos, toks = serve.run_chunk(
            serve.decode_chunk(model, k), params, token, states, pos)
        assert toks.shape[0] == k
    assert (fresh.value - f0, in_place.value - i0) == (1, K + 2)
