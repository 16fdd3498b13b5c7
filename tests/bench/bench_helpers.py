"""A tiny cell of the benchmark's configuration, for CPU tests: the same
block kind and structure (GQA 2:1 with QKV bias) at widths a test can
hold."""
import copy

TINY_MODEL = {
    "qwen2_7b": {
        "name": "qwen2-tiny", "family": "dense", "n_layers": 2, "d_model": 64, "n_heads": 4,
        "n_kv_heads": 2, "d_ff": 128, "vocab_size": 512, "qkv_bias": True,
        "rope_theta": 1000000.0, "group": ["attn"], "norm": "rmsnorm", "ffn": "swiglu",
        "param_dtype": "bfloat16", "compute_dtype": "bfloat16", "vocab_pad_multiple": 16},
}
REFERENCE = {"qwen2_7b": "qwen2_dense"}
PEAKS = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}


def tiny_cell(arch: str, limit: float, *, float32: bool = False) -> dict:
    """A cell dict as ``chipbench.catalog.cell`` returns it, at tiny size:
    two prompt lengths, batch 2, 12 decoded tokens (every decode k and a
    stream tail)."""
    model = copy.deepcopy(TINY_MODEL[arch])
    if float32:
        model["param_dtype"] = model["compute_dtype"] = "float32"
    return {
        "name": f"{arch}.tiny", "config": arch, "engine": "closed_batch", "chips": 1,
        "config_file": {"model_config": model, "reference": REFERENCE[arch]},
        "traffic_spec": {"loop": "closed", "batch": 2, "prompt_mix": [[8, 1], [16, 1]],
                         "gen": 12, "max_len": 28},
        "check": {"sample": 3, "limits": {"max_logit_gap": limit}},
    }
