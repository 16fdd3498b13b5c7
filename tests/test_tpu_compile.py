"""Every registered Pallas kernel compiles for a TPU v5e at real widths.

The TPU compiler compiles for a described chip with none attached, so these
run on the CPU backend: each kernel at the widths ``chip_smoke.py`` runs, with
its registered default tiles and with the smallest tile of its search space.
The topology is described only inside the fixture, never while a module is
imported: a process that describes it holds the TPU library until it exits.
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as kops
from repro.kernels.autotuned import get_spec, registered

BF, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32

#: call shapes per kernel: Qwen2-7B (FFN 3584 -> 18944; 28 heads, 4 KV heads,
#: head dim 128; decode batch 8), RWKV6-7B (64 heads of 64),
#: RecurrentGemma-2B (recurrence width 2560)
SHAPES = {
    "matmul": [((2048, 3584), BF), ((3584, 18944), BF)],
    "flash_attention": [((1, 4096, 28, 128), BF), ((1, 4, 4096, 128), BF), ((1, 4, 4096, 128), BF)],
    "decode_attention": [((8, 28, 128), BF), ((8, 4, 4096, 128), BF), ((8, 4, 4096, 128), BF),
                         ((8, 4096), I32)],
    "rwkv_scan": [((1, 1024, 64, 64), F32)] * 4 + [((64, 64), F32), ((1, 64, 64, 64), F32)],
    "lru_scan": [((2, 1024, 2560), F32), ((2, 1024, 2560), F32), ((2, 2560), F32)],
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    own_log_dir = "TPU_LOG_DIR" not in os.environ
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep these out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()
    if own_log_dir:
        os.environ.pop("TPU_LOG_DIR", None)


def test_every_registered_kernel_has_shapes():
    assert sorted(SHAPES) == registered()


@pytest.mark.parametrize("tiles", ["defaults", "smallest"])
@pytest.mark.parametrize("name", sorted(SHAPES))
def test_kernel_compiles_for_v5e(one_chip, name, tiles):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in SHAPES[name]]
    spec = get_spec(name)
    if tiles == "defaults":
        knobs = spec.defaults(*args)
    else:
        knobs = {d.name: d.lo for d in spec.space(*args).dims}
    fn = functools.partial(spec.fn, **knobs, interpret=False)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), f"{name} {knobs}: no Pallas kernel"


#: the decode cell's shapes: Qwen2-7B at published widths and bf16 weights,
#: cut to a few layers so the compiles stay quick (but not so few that the
#: stacked cache fits in on-chip memory); batch 32, cache 1536
DECODE_LAYERS, DECODE_B, DECODE_W = 4, 32, 1536


def _op_lines(text, shape):
    """(op, result type) of every instruction whose result holds ``shape``,
    outside the bodies of fusions (whose ops run inside the fusion)."""
    fused = set(re.findall(r"calls=%([\w.-]+)", text))
    for comp in re.split(r"\n(?=\S)", text):
        name = re.match(r"(?:ENTRY )?%?([\w.-]*)", comp).group(1)
        if name in fused:
            continue
        for line in comp.splitlines()[1:]:
            m = re.match(r"\s*(?:ROOT )?%\S+ = (.+?) ([\w-]+)\(", line)
            if m and shape in m.group(1):
                yield m.group(2), m.group(1)


@pytest.fixture(scope="module")
def decode_programs(one_chip):
    from repro import configs
    from repro.launch import serve
    from repro.models import ExecConfig, Model

    cfg = dataclasses.replace(configs.get("qwen2_7b", n_layers=DECODE_LAYERS),
                              param_dtype="bfloat16")
    model = Model(cfg, ExecConfig(rec_chunk=4))

    def spec(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
                            tree)

    params = spec(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    states = spec(jax.eval_shape(lambda: model.init_states(DECODE_B, DECODE_W, mode="decode")))
    token = jax.ShapeDtypeStruct((DECODE_B, 1), I32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), I32, sharding=one_chip)
    k_cache = states[0]["pos0"]["kv"]["k"]
    shape = "bf16[" + ",".join(map(str, k_cache.shape)) + "]"
    cache_bytes = 2 * k_cache.size * k_cache.dtype.itemsize  # K and V
    progs = {k: serve.decode_chunk(model, k).lower(params, token, states, pos).compile()
             for k in (1, 8)}
    return progs, shape, cache_bytes


def test_decode_chunk_writes_the_cache_once_for_v5e(decode_programs):
    """A chunk writes the stacked KV cache whole once: k=1 by its step's
    write-back (no copy), k=8 by the copy XLA makes of its input at entry,
    its steps then updating that copy in place, one slot a layer, with no
    copy of the cache inside a loop."""
    progs, shape, cache_bytes = decode_programs
    text = {k: ex.as_text() for k, ex in progs.items()}
    assert not [op for op, _ in _op_lines(text[1], shape) if op.startswith("copy")]
    writes = [t for op, t in _op_lines(text[1], shape)
              if op in ("fusion", "dynamic-update-slice") and "S(1)" not in t]
    assert len(writes) <= 2, writes  # the K and V write-back of the one step
    entry = text[8][text[8].index("\nENTRY "):]
    copies = [op for op, _ in _op_lines(text[8], shape) if op.startswith("copy")]
    assert copies == [op for op, _ in _op_lines(entry, shape) if op.startswith("copy")]
    assert len(copies) <= 2, copies  # K and V, at entry only
    # steps that wrote the cache out of place inside the loop held two more
    # copies of it as temporaries (1.87 GB at 14 layers); what stays is a
    # relayout of attention weights
    assert progs[8].memory_analysis().temp_size_in_bytes < cache_bytes / 2


#: the prefill cell's longest prompt: batch 1, 4096 tokens into a cache of
#: 4112, Qwen2-7B at published widths cut to one layer
PREFILL_B, PREFILL_S, PREFILL_W = 1, 4096, 4112


def test_prefill_attention_runs_the_flash_kernel_for_v5e(one_chip):
    """The engine's prefill compiled for a v5e runs its attention as the
    Pallas kernel (a custom call under scope ``attn``) and holds no S x S
    float32 scores.  Its temporaries (1.91 GB on the XLA path) are then the
    FFN's bf16 activation of S x d_ff (155 MB) and less than 64 MB besides."""
    from repro import configs
    from repro.models import ExecConfig, Model

    cfg = dataclasses.replace(configs.get("qwen2_7b", n_layers=1), param_dtype="bfloat16")
    model = Model(cfg, ExecConfig(rec_chunk=4))

    def prefill_first_token(params, tokens):
        hidden, states = model.prefill(params, {"tokens": tokens, "max_len": PREFILL_W})
        logits = model.logits(params, hidden[:, None])[:, 0]
        return jnp.argmax(logits, -1).astype(I32)[:, None], states

    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((PREFILL_B, PREFILL_S), I32, sharding=one_chip)
    with kops.targeting("tpu"):  # the described chip is not the default backend
        compiled = jax.jit(prefill_first_token).lower(params, tokens).compile()
    text = compiled.as_text()
    kernels = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert any(re.search(r'op_name="[^"]*\battn\b', line) for line in kernels), kernels
    g = cfg.n_heads // cfg.n_kv_heads
    scores = f"f32[{PREFILL_B},{cfg.n_kv_heads},{g},{PREFILL_S},{PREFILL_S}]"
    assert scores not in text
    ffn_act = PREFILL_B * PREFILL_S * cfg.d_ff * 2
    assert compiled.memory_analysis().temp_size_in_bytes < ffn_act + (64 << 20)


def test_flash_attention_compiles_for_v5e_with_narrow_heads(one_chip):
    """Heads narrower than a lane tile (SeamlessM4T's 16 heads of 64, no
    grouping) compile too."""
    q = jax.ShapeDtypeStruct((1, 2048, 16, 64), BF, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 16, 2048, 64), BF, sharding=one_chip)
    spec = get_spec("flash_attention")
    fn = functools.partial(spec.fn, **spec.defaults(q, kv, kv), interpret=False)
    assert "tpu_custom_call" in jax.jit(fn).lower(q, kv, kv).compile().as_text()
