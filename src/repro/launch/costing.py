"""Dry-run cost accounting.

XLA's ``compiled.cost_analysis()`` counts a while-loop body ONCE, so a
scan-over-layers program under-reports FLOPs/bytes/collectives by ~n_layers.
This module corrects that exactly:

    total = reported(step) + Σ_stages (n_groups_s - 1) × probe(stage body_s)

where ``probe`` lowers ONE stage body in isolation (same shapes, same mesh,
same sharding rules, same remat policy; value-and-grad of the body for train
steps so the backward scan body is included) and reads its cost_analysis +
HLO collective bytes.  The RWKV chunk loop is unrolled in dry-run lowering
(``ExecConfig.rec_unroll``) so no nested while remains.  Validated against a
fully-unrolled lowering in tests/test_dryrun_small.py.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.costs import collective_bytes, hlo_flops_bytes
from repro.parallel.api import ShardingRules, logical_spec, sharding_context
from repro.parallel.sharding import param_wanted, state_wanted, tree_shardings
from jax.sharding import NamedSharding, PartitionSpec

__all__ = ["CostTerms", "measure", "stage_probe", "corrected_cost"]


@dataclasses.dataclass
class CostTerms:
    flops: float = 0.0
    bytes_accessed: float = 0.0
    coll_bytes: float = 0.0

    def __add__(self, o):
        return CostTerms(
            self.flops + o.flops,
            self.bytes_accessed + o.bytes_accessed,
            self.coll_bytes + o.coll_bytes,
        )

    def scaled(self, f: float):
        return CostTerms(self.flops * f, self.bytes_accessed * f, self.coll_bytes * f)

    def as_dict(self):
        return dataclasses.asdict(self)


def measure(compiled, hlo_text: Optional[str] = None) -> CostTerms:
    flops, nbytes = hlo_flops_bytes(compiled)
    text = hlo_text if hlo_text is not None else compiled.as_text()
    return CostTerms(flops, nbytes, float(collective_bytes(text)))


def _slice0(tree):
    """ShapeDtypeStruct tree: drop the leading (group-stack) dim."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), tree
    )


def stage_probe(
    model,
    si: int,
    mesh,
    rules: ShardingRules,
    *,
    B: int,
    S: int,
    mode: str,
    train: bool,
    ctx_tokens: int = 0,
    encoder: bool = False,
) -> CostTerms:
    """Lower one stage body (fwd, or fwd+bwd for train) and return its cost."""
    cfg = model.cfg
    stage_defs = [(("attn",), cfg.enc_layers)] if encoder else model.stage_defs
    kinds, ng = stage_defs[si]
    cdt = jnp.dtype(cfg.compute_dtype)

    params_tree = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    stages = params_tree["encoder"]["stages"] if encoder else params_tree["stages"]
    gp_spec = _slice0(stages[si])
    states = jax.eval_shape(
        lambda: model._init_states_for(stage_defs, B, S if mode != "decode" else S, mode)
    )
    gst_spec = _slice0(states[si])
    x_spec = jax.ShapeDtypeStruct((B, 1 if mode == "decode" else S, cfg.d_model), cdt)
    ctx_spec = (
        jax.ShapeDtypeStruct((B, ctx_tokens, cfg.d_model), cdt) if ctx_tokens else None
    )
    q_pos = jax.ShapeDtypeStruct((1 if mode == "decode" else S,), jnp.int32)
    causal = not encoder

    def run_body(gp, x, gst, q_pos, ctx):
        body = model.make_stage_body(kinds, q_pos=q_pos, ctx=ctx, mode=mode, causal=causal)
        (x2, aux), st = body((x, jnp.zeros((), jnp.float32)), (gp, gst))
        return x2, aux, st

    def grad_body(gp, x, gst, q_pos, ctx):
        def scalar(gp_, x_):
            x2, aux, _ = run_body(gp_, x_, gst, q_pos, ctx)
            return jnp.sum(x2.astype(jnp.float32)) + aux

        val, grads = jax.value_and_grad(scalar, argnums=(0, 1))(gp, x)
        return val, grads

    # shardings: params via the stage rules (prepend the stripped stack dim),
    # activations dp-sharded, states via state rules.
    def gp_wanted(path, shape):
        return param_wanted("stages/0/" + path, len(shape) + 1)[1:]

    def gst_wanted(path, shape):
        return state_wanted("0/" + path, len(shape) + 1,
                            tp_size=mesh.shape.get("model", 0))[1:]

    gp_sh = tree_shardings(mesh, rules, gp_spec, gp_wanted)
    gst_sh = tree_shardings(mesh, rules, gst_spec, gst_wanted)
    x_sh = NamedSharding(mesh, logical_spec(mesh, rules, x_spec.shape, ("dp", "sp", None)))
    pos_sh = NamedSharding(mesh, PartitionSpec())
    args = [gp_spec, x_spec, gst_spec, q_pos]
    shardings = [gp_sh, x_sh, gst_sh, pos_sh]
    if ctx_spec is not None:
        args.append(ctx_spec)
        shardings.append(
            NamedSharding(mesh, logical_spec(mesh, rules, ctx_spec.shape, ("dp", None, None)))
        )
        fwd_fn, grd_fn = run_body, grad_body
    else:
        fwd_fn = lambda gp, x, gst, q_pos: run_body(gp, x, gst, q_pos, None)
        grd_fn = lambda gp, x, gst, q_pos: grad_body(gp, x, gst, q_pos, None)

    # out_shardings matter: without them GSPMD may back-propagate a
    # replicated output layout through the whole body (measured 100x flops
    # inflation on MoE probes).
    st_sh = tree_shardings(mesh, rules, jax.eval_shape(fwd_fn, *args)[2], gst_wanted)
    aux_sh = NamedSharding(mesh, PartitionSpec())
    fwd_out_sh = (x_sh, aux_sh, st_sh)
    grd_out_sh = (aux_sh, (gp_sh, x_sh))

    def _measure(fn, out_sh):
        with sharding_context(mesh, rules):
            lowered = jax.jit(
                fn, in_shardings=tuple(shardings), out_shardings=out_sh
            ).lower(*args)
        return measure(lowered.compile())

    if not train:
        return _measure(fwd_fn, fwd_out_sh)
    g = _measure(grd_fn, grd_out_sh)
    if model.exec_cfg.remat in ("full", "dots"):
        # the scan's backward pass re-runs the (checkpointed) forward; a
        # straight-line grad program CSE's that recompute away, so add the
        # forward cost explicitly ("dots" recompute is bounded above by full).
        f = _measure(fwd_fn, fwd_out_sh)
        g = g + f
    return g


def corrected_cost(model, step_cost: CostTerms, probes: dict) -> CostTerms:
    """total = step + Σ (ng-1) × probe (+ (enc_layers-1) × encoder probe)."""
    total = step_cost
    for si, (kinds, ng) in enumerate(model.stage_defs):
        if ng > 1 and si in probes:
            total = total + probes[si].scaled(ng - 1)
    if "encoder" in probes and model.cfg.enc_layers > 1:
        total = total + probes["encoder"].scaled(model.cfg.enc_layers - 1)
    return total
