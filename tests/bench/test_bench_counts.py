"""``chipbench/counts.py``: operations and bytes from shapes, against the
program's own parameter trees and hand counts."""
import jax
import numpy as np
import pytest

from chipbench import catalog, counts
from repro.models import Model


def _tree(arch):
    cfg_file = catalog.cell({"qwen2_7b": "qwen2_7b.decode"}[arch])["config_file"]
    cfg = catalog.model_config(cfg_file)
    return cfg_file["model_config"], jax.eval_shape(Model(cfg).init, jax.random.PRNGKey(0))


@pytest.mark.parametrize("arch,kind", [("qwen2_7b", "attn")])
def test_parameter_counts_match_the_program(arch, kind):
    mc, params = _tree(arch)
    stage = params["stages"][0]["pos0"]
    per_layer = sum(int(np.prod(a.shape[1:])) for a in jax.tree.leaves(stage))
    assert counts.layer_params(kind, mc) == per_layer
    total = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    embed = params["embed"]["table"]
    # every weight but the embedding table, whose rows a step reads one per token
    assert counts.weight_bytes(mc) == total - embed.size * embed.dtype.itemsize


def test_qwen2_7b_published_sizes():
    mc, _ = _tree("qwen2_7b")
    # 233.0M weights a layer, 14 layers, 545M in the LM head (bf16)
    assert counts.layer_matmul_params("attn", mc) == 3584 * 3584 * 2 + 2 * 3584 * 512 + 3 * 3584 * 18944
    assert abs(counts.weight_bytes(mc) / 1e9 - 7.61) < 0.01


def test_decode_counts_by_hand():
    mc = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1, "d_ff": 16,
          "vocab_size": 32, "group": ["attn"], "param_dtype": "bfloat16",
          "compute_dtype": "bfloat16"}
    lin = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16  # q, k+v (1 kv head of 4), o, ffn
    # 3 rows at position 5: each row attends 6 keys per layer (4 flops per key per head dim)
    want = 3 * (2 * 8 * 32 + 2 * (2 * lin + 4 * 2 * 4 * 6))
    assert counts.decode_step_flops(mc, 3, 5) == want
    # bytes: weights + 3 embedding rows + per layer K and V of 6 valid entries read and 1 written
    kv = 2 * 3 * 1 * 4 * 2 * 7
    assert counts.decode_step_bytes(mc, 3, 5) == counts.weight_bytes(mc) + 3 * 8 * 2 + 2 * kv


def test_prefill_attention_is_causal_half():
    mc = {"n_layers": 1, "d_model": 8, "n_heads": 2, "n_kv_heads": 2, "d_ff": 16,
          "vocab_size": 32, "group": ["attn"]}
    lin = counts.layer_matmul_params("attn", mc)
    S = 10
    assert counts.prefill_flops(mc, 1, S) == 2 * 8 * 32 + 2 * lin * S + 4 * 2 * 4 * (S * (S + 1) // 2)
    assert counts.prefill_flops(mc, 3, S) == 3 * counts.prefill_flops(mc, 1, S)

