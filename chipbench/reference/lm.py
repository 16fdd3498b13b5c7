"""Plain float32 language-model reference: embedding, the configuration's
layers one at a time, final norm and LM head.

It imports nothing of the program.  Its weights are drawn again from the
run's seed by ``chipbench.weights`` under the program's leaf names, layer by
layer, so that only one layer's float32 weights live on the device at a
time.  Every product with a weight, and attention's two, goes through an
``Arith``: ``EXACT`` is float32 at ``highest`` precision; ``FP8`` rounds both
operands to float8 e4m3 (per-tensor scale) first — the control that a served
model computed one precision step below bfloat16 must fail.

A block module (``reference/<name>.py``) gives ``PREFIX`` (its leaf names'
prefix in the program's tree), ``layer_shapes(cfg)`` and
``apply(cfg, w, x, arith)`` over a whole teacher-forced sequence.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights

HIGHEST = jax.lax.Precision.HIGHEST


class Arith:
    """How the reference contracts: exact float32, or through float8."""

    def __init__(self, name: str, quantize=None):
        self.name = name
        self._q = quantize

    def einsum(self, spec: str, a, b):
        if self._q is not None:
            a, b = self._q(a), self._q(b)
        return jnp.einsum(spec, a, b, precision=HIGHEST,
                          preferred_element_type=jnp.float32)


def _fp8(t):
    t = t.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / 448.0
    return (t / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


EXACT = Arith("float32")
FP8 = Arith("float8_e4m3", _fp8)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


class LM:
    """The reference model of one configuration and one seed."""

    def __init__(self, cfg: dict, block, seed: int):
        self.cfg = cfg
        self.block = block
        self.key = weights.seed_key(seed)
        self.pdt = jnp.dtype(cfg["param_dtype"])
        d, V = int(cfg["d_model"]), int(cfg["vocab_size"])
        self.d, self.V = d, V
        shapes = block.layer_shapes(cfg)
        self._layer = jax.jit(lambda key, i: {
            n: a.astype(jnp.float32) for n, a in weights.layer_params(
                key, block.PREFIX, i, shapes, self.pdt).items()})
        self._apply = jax.jit(functools.partial(block.apply, cfg), static_argnums=(2,))
        self._draw = jax.jit(weights.global_param, static_argnums=(1, 2, 3))
        self._head = None

    def _global(self, name, shape):
        return self._draw(self.key, name, tuple(shape), self.pdt).astype(jnp.float32)

    def hidden(self, tokens: np.ndarray, first: int, arith: Arith):
        """Final-normed hidden states ``(B, T - first, d)`` of positions
        ``first..T-1`` of the teacher-forced ``tokens`` (B, T)."""
        table = self._draw(self.key, "embed/table", (self.V, self.d), self.pdt)
        x = jnp.take(table, jnp.asarray(tokens), axis=0).astype(jnp.float32)
        del table
        with jax.default_matmul_precision("highest"):
            for i in range(int(self.cfg["n_layers"])):
                x = self._apply(self._layer(self.key, i), x, arith)
        x = x[:, first:]
        return rmsnorm(x, self._global("final_norm/scale", (self.d,)), self.block.EPS)

    def head(self):
        if self._head is None:
            self._head = self._global("lm_head/w", (self.d, self.V))
        return self._head


@jax.jit
def _gap_stats(h_ref, head, picks):
    """Per position: the reference's best logit minus its logit of each
    picked token, for ``picks`` (n, m) token ids."""
    with jax.default_matmul_precision("highest"):
        lg = EXACT.einsum("nd,dv->nv", h_ref, head)
    best = jnp.max(lg, -1)
    return best[:, None] - jnp.take_along_axis(lg, picks, axis=-1)


@jax.jit
def _first_choice(h, head):
    with jax.default_matmul_precision("highest"):
        return jnp.argmax(FP8.einsum("nd,dv->nv", h, head), -1).astype(jnp.int32)


def served_gaps(lm: LM, prompt: np.ndarray, served: np.ndarray, *, control: bool = False) -> dict:
    """Teacher-force ``prompt`` ++ ``served[:-1]`` (one row at a time) and,
    at each position that produced a served token, the gap by which that
    token's reference logit lies below the reference's best.  With
    ``control`` also the gap of the token the float8 control puts first
    at the same positions.  Returns ``{"served": gaps[, "control": gaps]}``
    as float32 numpy vectors, one entry per served token."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])[None]
    first = prompt.size - 1
    head = lm.head()
    h = lm.hidden(seq, first, EXACT)[0]
    picks = [served]
    if control:
        hc = lm.hidden(seq, first, FP8)[0]
        picks.append(np.asarray(_first_choice(hc, head)))
    gaps = np.asarray(_gap_stats(h, head, jnp.asarray(np.stack(picks, 1))))
    out = {"served": gaps[:, 0]}
    if control:
        out["control"] = gaps[:, 1]
    return out
