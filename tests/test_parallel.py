"""Distribution tests: sharding rules, logical specs, collectives, pipeline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.parallel.api import ShardingRules, logical_spec
from repro.parallel.sharding import param_wanted, state_wanted

from helpers import run_py


# ------------------------------------------------------ sharding rule units
def test_param_wanted_attention():
    assert param_wanted("stages/0/pos0/attn/wq/w", 3) == (None, "fsdp", "tp")
    assert param_wanted("stages/0/pos0/attn/wo/w", 3) == (None, "tp", "fsdp")
    assert param_wanted("stages/0/pos0/attn/wq/b", 2) == (None, "tp")
    assert param_wanted("embed/table", 2) == ("tp", "fsdp")
    assert param_wanted("lm_head/w", 2) == ("fsdp", "tp")


def test_param_wanted_moe_vs_dense():
    # expert weights (ng, E, D, F) -> EP on experts
    assert param_wanted("stages/0/pos0/ffn/wi", 4) == (None, "ep", "fsdp", None)
    assert param_wanted("stages/0/pos0/ffn/wo", 4) == (None, "ep", None, "fsdp")
    # dense ffn (ng, D, F)
    assert param_wanted("stages/0/pos0/ffn/wi", 3) == (None, "fsdp", "tp")
    assert param_wanted("stages/0/pos0/ffn/dense/wi", 3) == (None, "fsdp", "tp")
    assert param_wanted("stages/0/pos0/ffn/router", 3) == (None, "fsdp", None)


def test_param_wanted_norms_replicated():
    assert param_wanted("stages/0/pos0/norm1/scale", 2) == (None, None)
    assert param_wanted("final_norm/scale", 1) == (None,)


def test_state_wanted():
    assert state_wanted("0/pos0/kv/k", 5) == (None, "dp", "tp", None, None)
    # GQA kv=8 on 16-way model axis: prefer the sharded-sequence KV layout
    assert state_wanted("0/pos0/kv/k", (126, 128, 8, 32768, 128), tp_size=16) == (
        None, "dp", None, "tp", None)
    assert state_wanted("0/pos0/kv/k", (126, 128, 16, 32768, 128), tp_size=16) == (
        None, "dp", "tp", None, None)
    assert state_wanted("0/pos0/kv/pos", 2) == (None, None)
    assert state_wanted("0/pos0/wkv", 5) == (None, "dp", "tp", None, None)
    assert state_wanted("0/pos0/h", 3) == (None, "dp", "tp")
    assert state_wanted("0/pos0/conv", 4) == (None, "dp", None, "tp")


@pytest.mark.multidevice
def test_logical_spec_divisibility_guard():
    """Dims that don't divide the axis product must replicate, not crash."""
    code = """
import jax
from repro.launch.mesh import make_mesh
from repro.parallel.api import ShardingRules, logical_spec
from jax.sharding import PartitionSpec as P

mesh = make_mesh((2, 4), ("data", "model"))
rules = ShardingRules(dp=("data",), tp="model", fsdp=("data",))
# 28 heads on a 4-way model axis -> sharded; 30 -> replicated
assert logical_spec(mesh, rules, (28, 64), ("tp", None)) == P("model", None)
assert logical_spec(mesh, rules, (30, 64), ("tp", None)) == P(None, None)
# batch 1 cannot shard over dp
assert logical_spec(mesh, rules, (1, 5), ("dp", None)) == P(None, None)
print("OK")
"""
    assert "OK" in run_py(code, devices=8)


# ------------------------------------------------------------- collectives
@pytest.mark.multidevice
def test_int8_psum_and_topk():
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.parallel.collectives import int8_psum, topk_psum

mesh = make_mesh((4,), ("data",))
g = jax.random.normal(jax.random.PRNGKey(0), (4, 64))

def f8(gl):
    return int8_psum(gl[0], "data")
out = jax.shard_map(f8, mesh=mesh, in_specs=(P("data", None),), out_specs=P(), check_vma=False)(g)
exact = jnp.sum(g, axis=0)
rel = float(jnp.max(jnp.abs(out - exact)) / (jnp.max(jnp.abs(exact)) + 1e-9))
assert rel < 0.05, rel

def ftk(gl, el):
    r, ne = topk_psum(gl[0], "data", 0.25, el[0])
    return r, ne[None]
err0 = jnp.zeros((4, 64))
out, ne = jax.shard_map(ftk, mesh=mesh, in_specs=(P("data", None), P("data", None)),
                    out_specs=(P(), P("data", None)), check_vma=False)(g, err0)
# error feedback: sparse + residual == original (per shard)
recon = out  # sum of sparse parts
# after two rounds with error feedback the cumulative reduction approaches exact
r2, ne2 = jax.shard_map(ftk, mesh=mesh, in_specs=(P("data", None), P("data", None)),
                    out_specs=(P(), P("data", None)), check_vma=False)(jnp.zeros_like(g), ne)
total = out + r2
gap1 = float(jnp.linalg.norm(out - exact))
gap2 = float(jnp.linalg.norm(total - exact))
assert gap2 < gap1, (gap1, gap2)   # residual shrinks with error feedback
print("OK")
"""
    assert "OK" in run_py(code, devices=4)


def test_wire_bytes_model():
    from repro.parallel.collectives import wire_bytes

    tree = {"a": jnp.zeros((1000,)), "b": jnp.zeros((24,))}
    assert wire_bytes(tree, "fp32") == 2 * 4 * 1024
    assert wire_bytes(tree, "int8") < wire_bytes(tree, "bf16") < wire_bytes(tree, "fp32")
    assert wire_bytes(tree, "topk", 0.01) < wire_bytes(tree, "int8")


# ---------------------------------------------------------------- pipeline
@pytest.mark.multidevice
def test_gpipe_matches_sequential():
    """4-stage pipeline over a 4-device axis == sequential application."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.parallel.pipeline import gpipe_apply

mesh = make_mesh((4,), ("pod",))
S, M, mb, d = 4, 6, 3, 16
ks = jax.random.split(jax.random.PRNGKey(0), 2)
w = jax.random.normal(ks[0], (S, d, d)) / np.sqrt(d)
x = jax.random.normal(ks[1], (M, mb, d))

def stage_fn(wp, xmb):
    return jnp.tanh(xmb @ wp)

y = gpipe_apply(mesh, "pod", stage_fn, w, x)
# sequential oracle
want = x
for s in range(S):
    want = jnp.tanh(want @ w[s])
np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)

# grads flow through ppermute
def loss(w):
    return jnp.sum(gpipe_apply(mesh, "pod", stage_fn, w, x) ** 2)
g = jax.grad(loss)(w)
def loss_seq(w):
    h = x
    for s in range(4):
        h = jnp.tanh(h @ w[s])
    return jnp.sum(h ** 2)
gs = jax.grad(loss_seq)(w)
np.testing.assert_allclose(np.asarray(g), np.asarray(gs), atol=1e-4)
print("OK")
"""
    assert "OK" in run_py(code, devices=4, timeout=900)
