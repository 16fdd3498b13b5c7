"""Seeded random weights, made on the device, identical for the program and
the reference.

Every leaf is named by its path in the program's parameter tree
(``stages/0/pos0/attn/wq/w``); a stacked leaf of a layer stage is drawn
layer by layer, with the layer index folded into its key.  The program gets
the whole tree from one jitted call in the served dtype; the reference
draws the same leaves again, one layer at a time, from the same seed and
names, and never sees the program's arrays.

How a leaf is drawn depends only on its last name component (N(s) is a
near-normal draw of standard deviation s, rounded to a power of two):

* ``scale`` (norm gains): 1 + N(0.1); ``b``/``bias``: N(0.1)
* ``table`` (embedding): N(1)
* any other leaf is a matrix: N(1 / sqrt(fan_in)), fan_in = ``shape[-2]``

Draws use integer arithmetic and powers of two only, so each value is
exact in float32 before its one rounding to the served dtype, and the
program's draw and the reference's agree bit for bit however each is
compiled.

Norm gains and biases are drawn away from their usual constant
inits, so that a program that dropped one of them would not agree with
the reference by accident.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any whole number (more bits than int32 holds)."""
    s = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(0)
    for part in (s & 0x7FFFFFFF, (s >> 31) & 0x7FFFFFFF, s >> 62):
        key = jax.random.fold_in(key, part)
    return key


def leaf_key(key, name: str, layer=None):
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return k if layer is None else jax.random.fold_in(k, layer)


#: standard deviation of the sum of four independent 6-bit uniforms
_IH_STD = float(np.sqrt(4 * (64 ** 2 - 1) / 12))


def _pow2(x: float) -> float:
    return float(2.0 ** round(np.log2(x)))


def _normal(key, shape, std: float):
    """Near-normal draws from integer arithmetic alone: the centred sum of
    four 6-bit uniforms (an integer in [-126, 126]) times a power of two
    close to ``std / 36.9``.  Every value is exact in float32 and in
    bfloat16, so no compilation of the draw can round it differently."""
    b = jax.random.bits(key, shape, jnp.uint32)
    s = sum(((b >> (8 * i)) & 63).astype(jnp.int32) for i in range(4)) - 126
    return s.astype(jnp.float32) * _pow2(std / _IH_STD)


def draw(key, name: str, shape: tuple, dtype) -> jnp.ndarray:
    """One leaf, computed exactly in float32 and rounded once to ``dtype``."""
    last = name.rsplit("/", 1)[-1]
    if last == "scale":
        x = 1.0 + _normal(key, shape, 0.1)
    elif last in ("b", "bias"):
        x = _normal(key, shape, 0.1)
    elif last == "table":
        x = _normal(key, shape, 1.0)
    else:
        if len(shape) < 2:
            raise ValueError(f"no rule for leaf {name!r} of shape {shape}")
        x = _normal(key, shape, 1.0 / np.sqrt(shape[-2]))
    return x.astype(dtype)


def _path_name(path) -> str:
    parts = []
    for p in path:
        for attr in ("key", "idx", "name"):
            if hasattr(p, attr):
                parts.append(str(getattr(p, attr)))
                break
    return "/".join(parts)


def program_params(shapes, seed: int):
    """The program's whole parameter tree, from one jitted call on the
    default device.  ``shapes``: the tree of ``ShapeDtypeStruct`` that
    ``jax.eval_shape(model.init, key)`` gives; leaves under ``stages`` carry
    a leading layer axis and are drawn one layer at a time."""
    named = jax.tree_util.tree_flatten_with_path(shapes)
    leaves, treedef = named
    names = [_path_name(p) for p, _ in leaves]

    def make(key):
        out = []
        for name, (_, sds) in zip(names, leaves):
            if name.startswith("stages/"):
                out.append(jax.lax.map(
                    lambda i, name=name, sds=sds: draw(
                        leaf_key(key, name, i), name, sds.shape[1:], sds.dtype),
                    jnp.arange(sds.shape[0])))
            else:
                out.append(draw(leaf_key(key, name), name, sds.shape, sds.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(seed_key(seed))


def layer_params(key, prefix: str, layer, shapes: dict, dtype) -> dict:
    """One layer's leaves ``{name: array}`` as the program holds them (in
    ``dtype``), for names ``prefix + name`` and layer index ``layer``;
    ``key`` is :func:`seed_key` of the run's seed."""
    return {n: draw(leaf_key(key, prefix + n, layer), prefix + n, tuple(s), dtype)
            for n, s in shapes.items()}


def global_param(key, name: str, shape: tuple, dtype):
    """A leaf outside the layer stages (embedding, final norm, LM head)."""
    return draw(leaf_key(key, name), name, tuple(shape), dtype)
