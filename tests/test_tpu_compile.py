"""Every registered Pallas kernel compiles for a TPU v5e at real widths.

The TPU compiler compiles for a described chip with none attached, so these
run on the CPU backend: each kernel at the widths ``chip_smoke.py`` runs, with
its registered default tiles and with the smallest tile of its search space.
The topology is described only inside the fixture, never while a module is
imported: a process that describes it holds the TPU library until it exits.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

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
