"""GPipe-style pipeline parallelism over a mesh axis (shard_map + ppermute).

The stage axis (``pod`` on the multi-pod mesh) holds a contiguous slice of
layer groups per device row.  Microbatches flow through the classic GPipe
schedule: at tick t, stage s processes microbatch (t - s); activations hop
stage→stage+1 via ``jax.lax.ppermute``.  Bubble fraction = (S-1)/(M+S-1).

Autodiff gives the backward schedule for free (ppermute transposes to the
reverse permutation), so this composes with jax.grad for training.  Used by
the dry-run ``--pp`` variant and tests/test_pipeline.py; the default
multi-pod config uses hierarchical DP over the pod axis instead (DESIGN §6).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["gpipe_apply"]


def gpipe_apply(
    mesh,
    axis: str,
    stage_fn: Callable,  # (stage_params, x_mb) -> y_mb
    stage_params,  # pytree; leaves with leading dim == n_stages (sharded over axis)
    x,  # (M, mb, ...) microbatched inputs (replicated over axis)
):
    """Run x through S pipeline stages; returns (M, mb, ...) outputs."""
    S = mesh.shape[axis]
    M = x.shape[0]

    def per_stage(params_local, x_all):
        # params_local: leaves (1, ...) — this stage's slice
        params_local = jax.tree.map(lambda a: a[0], params_local)
        sid = jax.lax.axis_index(axis)
        T = M + S - 1
        mb_shape = x_all.shape[1:]
        carry = jnp.zeros(mb_shape, x_all.dtype)
        ys = jnp.zeros_like(x_all)

        def tick(t, state):
            carry, ys = state
            # stage 0 ingests microbatch t (if still available)
            mb_in = x_all[jnp.minimum(t, M - 1)]
            inp = jnp.where(sid == 0, mb_in, carry)
            out = stage_fn(params_local, inp)
            # last stage emits microbatch (t - (S-1))
            oidx = jnp.clip(t - (S - 1), 0, M - 1)
            emit = (sid == S - 1) & (t >= S - 1)
            ys = jax.lax.dynamic_update_index_in_dim(
                ys, jnp.where(emit, out, ys[oidx]), oidx, axis=0
            )
            # shift activations one stage forward
            carry = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % S) for i in range(S)]
            )
            return carry, ys

        carry, ys = jax.lax.fori_loop(0, T, tick, (carry, ys))
        # only the last stage's ys are the real outputs; broadcast them
        ys = jnp.where(sid == S - 1, ys, jnp.zeros_like(ys))
        return jax.lax.psum(ys, axis)

    specs_params = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(specs_params, P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x)
