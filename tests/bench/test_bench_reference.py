"""The plain float32 references in ``chipbench/reference`` against the
program's ``models/`` at tiny sizes on the CPU: prefill then decoding
through the KV cache gives the reference's logits at every position, and
both draw the same weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from bench_helpers import tiny_cell

from chipbench import catalog, weights
from chipbench.reference import lm
from repro.models import Model

#: program in float32 vs the float32 reference: the same mathematics in a
#: different order (cache-masked attention), so only
#: rounding separates them; max |diff| over max |ref logit|
TOL_F32 = 1e-4


def _program_logits(cfg, params, prompt, gen):
    """Logits of the prompt's last position (prefill) and of each of ``gen``
    greedy decode steps after it, with the tokens they chose."""
    model = Model(cfg, catalog.engine("closed_batch").EXEC_CFG)
    tokens = jnp.asarray(prompt)[None]
    hidden, states = model.prefill(params, {"tokens": tokens, "max_len": prompt.size + gen})
    lg = model.logits(params, hidden[:, None])[:, 0]
    out, served = [lg[0]], [int(jnp.argmax(lg[0]))]
    pos = jnp.int32(prompt.size)
    for _ in range(gen):
        tok = jnp.asarray([[served[-1]]], jnp.int32)
        lg, states = model.decode_step(params, tok, states, pos)
        out.append(lg[0])
        served.append(int(jnp.argmax(lg[0])))
        pos = pos + 1
    return np.stack([np.asarray(x, np.float32) for x in out]), np.asarray(served, np.int32)


@pytest.mark.parametrize("arch", ["qwen2_7b"])
def test_prefill_and_decode_match_reference(arch):
    cell = tiny_cell(arch, 1.0, float32=True)
    cfg = catalog.model_config(cell["config_file"])
    seed = 2**33 + 17
    params = weights.program_params(jax.eval_shape(Model(cfg).init, jax.random.PRNGKey(0)), seed)
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, 16).astype(np.int32)
    got, served = _program_logits(cfg, params, prompt, gen=8)

    block = catalog.reference(cell["config_file"]["reference"])
    ref = lm.LM(cell["config_file"]["model_config"], block, seed)
    seq = np.concatenate([prompt, served[:-1]])[None]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.hidden(seq, prompt.size - 1, lm.EXACT)[0] @ ref.head())
    assert got.shape == want.shape == (9, cfg.vocab_size)
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err <= TOL_F32, err
    gaps = lm.served_gaps(ref, prompt, served)["served"]
    assert gaps.shape == (9,) and float(gaps.max()) <= TOL_F32 * np.max(np.abs(want))


@pytest.mark.parametrize("arch", ["qwen2_7b"])
def test_reference_draws_the_programs_weights(arch):
    cell = tiny_cell(arch, 1.0)
    cfg = catalog.model_config(cell["config_file"])
    seed = 123456789012
    params = weights.program_params(jax.eval_shape(Model(cfg).init, jax.random.PRNGKey(0)), seed)
    block = catalog.reference(cell["config_file"]["reference"])
    shapes = block.layer_shapes(cell["config_file"]["model_config"])
    key = weights.seed_key(seed)
    stage = params["stages"][0]["pos0"]
    for layer in range(cfg.n_layers):
        drawn = weights.layer_params(key, block.PREFIX, layer, shapes, jnp.bfloat16)
        for name, arr in drawn.items():
            leaf = stage
            for part in name.split("/"):
                leaf = leaf[part]
            np.testing.assert_array_equal(np.asarray(leaf[layer]), np.asarray(arr), err_msg=name)
    assert len(jax.tree.leaves(stage)) == len(shapes)
    for name, leaf in (("embed/table", params["embed"]["table"]),
                       ("lm_head/w", params["lm_head"]["w"]),
                       ("final_norm/scale", params["final_norm"]["scale"])):
        np.testing.assert_array_equal(
            np.asarray(leaf), np.asarray(weights.global_param(key, name, leaf.shape, jnp.bfloat16)))


def test_fp8_control_is_coarser_than_bf16():
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 256), jnp.float32)
    e8 = float(jnp.max(jnp.abs(lm._fp8(x) - x)))
    e16 = float(jnp.max(jnp.abs(x.astype(jnp.bfloat16).astype(jnp.float32) - x)))
    assert e8 > 4 * e16 > 0
