"""Model: embeddings → staged block stacks (scan-over-groups) → LM head.

One class serves all 10 assigned architectures: dense / MoE decoders,
attention-free RWKV-6, the Griffin 1:2 hybrid, the seamless encoder–decoder
(audio frontend stubbed as precomputed frame embeddings) and the llama-3.2
vision backbone (patch embeddings stubbed, cross-attention layers real).

Layer stacking: the repeating group is the body of a ``jax.lax.scan`` with
per-group stacked parameters, so a 126-layer model lowers to one small HLO
loop body; ``ExecConfig.scan_unroll`` / ``remat`` control the unroll factor
and activation-checkpoint policy (both PATSMA-tunable).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.parallel.api import constrain

from .blocks import apply_block, init_block, init_block_state
from .config import ExecConfig, ModelConfig
from .layers import embed_init, norm_apply, norm_init, _nrm

__all__ = ["Model"]


def _stack_init(fn, rng, n: int):
    """Initialize n copies of params with independent keys, stacked on axis 0."""
    return jax.vmap(fn)(jax.random.split(rng, n))


class Model:
    def __init__(self, cfg: ModelConfig, exec_cfg: ExecConfig = ExecConfig()):
        self.cfg = cfg
        self.exec_cfg = exec_cfg
        # stage definitions: [(kinds, n_groups)]
        self.stage_defs = []
        if cfg.n_groups > 0:
            self.stage_defs.append((cfg.group, cfg.n_groups))
        if cfg.tail:
            self.stage_defs.append((cfg.tail, 1))

    # ------------------------------------------------------------------ init
    def init(self, rng) -> dict:
        cfg = self.cfg
        keys = jax.random.split(rng, 8)
        params = {
            "embed": embed_init(keys[0], cfg.padded_vocab, cfg.d_model),
            "final_norm": norm_init(cfg.norm, cfg.d_model),
            "stages": [
                _stack_init(
                    lambda k, kinds=kinds: {
                        f"pos{i}": init_block(kind, jax.random.fold_in(k, i), cfg)
                        for i, kind in enumerate(kinds)
                    },
                    jax.random.fold_in(keys[1], si),
                    ng,
                )
                for si, (kinds, ng) in enumerate(self.stage_defs)
            ],
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = {
                "w": _nrm(keys[2], (cfg.d_model, cfg.padded_vocab), cfg.d_model**-0.5)
            }
        if cfg.is_encdec:
            enc_cfg = cfg  # same dims; bidirectional attn blocks
            params["encoder"] = {
                "stages": [
                    _stack_init(
                        lambda k: {"pos0": init_block("attn", k, enc_cfg)},
                        keys[3],
                        cfg.enc_layers,
                    )
                ],
                "norm": norm_init(cfg.norm, cfg.d_model),
            }
        p_dt = jnp.dtype(cfg.param_dtype)
        return jax.tree.map(lambda a: a.astype(p_dt), params)

    # ------------------------------------------------------------ stack exec
    def _run_stack(
        self,
        stage_defs,
        stages_params,
        x,
        states,
        *,
        q_pos,
        ctx,
        mode,
        causal,
        in_place=False,
    ):
        """Run every stage's scan over its groups.  Each stage's state goes in
        as the scan's ``xs`` and comes out freshly written as its ``ys``;
        with ``in_place`` (decode only) the stacked state rides in the carry
        instead and each layer writes only what its step changed."""
        ec = self.exec_cfg
        aux = jnp.zeros((), jnp.float32)
        new_states = []
        for si, (kinds, ng) in enumerate(stage_defs):
            unroll = max(1, min(ec.scan_unroll, ng))
            with jax.named_scope("layers"):
                if in_place:
                    (x, aux, st_out), _ = jax.lax.scan(
                        self._in_place_body(kinds, q_pos=q_pos),
                        (x, aux, states[si]),
                        (stages_params[si], jnp.arange(ng, dtype=jnp.int32)),
                        unroll=unroll,
                    )
                else:
                    body = self.make_stage_body(
                        kinds, q_pos=q_pos, ctx=ctx, mode=mode, causal=causal)
                    (x, aux), st_out = jax.lax.scan(
                        body, (x, aux), (stages_params[si], states[si]), unroll=unroll)
            new_states.append(st_out)
        return x, new_states, aux

    def _in_place_body(self, kinds, *, q_pos):
        """Decode scan body over a carried state: carry (x, aux, stage
        state); xs (group_params, group index)."""

        def body(carry, xs):
            xc, auxc, st = carry
            gp, layer = xs
            st = dict(st)
            for i, kind in enumerate(kinds):
                xc, st[f"pos{i}"], a = apply_block(
                    kind,
                    self.cfg,
                    gp[f"pos{i}"],
                    xc,
                    st[f"pos{i}"],
                    q_pos=q_pos,
                    ctx=None,
                    mode="decode",
                    causal=True,
                    exec_cfg=self.exec_cfg,
                    layer=layer,
                )
                auxc = auxc + a
            return (xc, auxc, st), None

        return body

    def make_stage_body(self, kinds, *, q_pos, ctx, mode, causal):
        """The per-group scan body: carry (x, aux); xs (group_params, group_state).
        Exposed so the dry-run cost probes can lower one body in isolation
        (cost_analysis counts while-loop bodies once; see launch/costing.py)."""
        ec = self.exec_cfg

        def body(carry, xs):
            xc, auxc = carry
            xc = constrain(xc, ("dp", "sp", None))
            gp, gst = xs
            out_st = {}
            for i, kind in enumerate(kinds):
                xc, st_i, a = apply_block(
                    kind,
                    self.cfg,
                    gp[f"pos{i}"],
                    xc,
                    gst.get(f"pos{i}"),
                    q_pos=q_pos,
                    ctx=ctx,
                    mode=mode,
                    causal=causal,
                    exec_cfg=ec,
                )
                out_st[f"pos{i}"] = st_i
                auxc = auxc + a
            return (xc, auxc), out_st

        if ec.remat == "full":
            body = jax.checkpoint(body)
        elif ec.remat == "dots":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            )
        return body

    # ------------------------------------------------------------ embeddings
    def embed(self, params, tokens):
        with jax.named_scope("embed"):
            return params["embed"]["table"].astype(jnp.dtype(self.cfg.compute_dtype))[tokens]

    def head_weights(self, params):
        if self.cfg.tie_embeddings:
            return params["embed"]["table"].T
        return params["lm_head"]["w"]

    def logits(self, params, x):
        with jax.named_scope("lm_head"):
            return x @ self.head_weights(params).astype(x.dtype)

    # --------------------------------------------------------------- encoder
    def encode(self, params, frames):
        """frames: (B, S_enc, D) precomputed embeddings (modality stub)."""
        B, S = frames.shape[:2]
        enc_defs = [(("attn",), self.cfg.enc_layers)]
        x = frames.astype(jnp.dtype(self.cfg.compute_dtype))
        states = self._init_states_for(enc_defs, B, S, mode="train")
        x, _, _ = self._run_stack(
            enc_defs,
            params["encoder"]["stages"],
            x,
            states,
            q_pos=jnp.arange(S),
            ctx=None,
            mode="train",
            causal=False,
        )
        return norm_apply(self.cfg.norm, params["encoder"]["norm"], x)

    def _context(self, params, batch: dict) -> Optional[jnp.ndarray]:
        if self.cfg.is_encdec:
            return self.encode(params, batch["frames"])
        if self.cfg.family == "vlm":
            return batch["ctx_embeds"].astype(jnp.dtype(self.cfg.compute_dtype))
        return None

    # ----------------------------------------------------------------- modes
    def forward(self, params, batch: dict) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Teacher-forced full-sequence pass.  batch["tokens"]: (B,S) inputs.
        Returns (hidden (B,S,D), aux_loss); logits via self.logits (or the
        chunked loss path in training, which never materializes them)."""
        tokens = batch["tokens"]
        B, S = tokens.shape
        ctx = self._context(params, batch)
        x = self.embed(params, tokens)
        states = self.init_states(B, S, mode="train")
        x, _, aux = self._run_stack(
            self.stage_defs,
            params["stages"],
            x,
            states,
            q_pos=jnp.arange(S),
            ctx=ctx,
            mode="train",
            causal=True,
        )
        x = norm_apply(self.cfg.norm, params["final_norm"], x)
        return x, aux

    def init_states(self, batch: int, max_len: int, mode: str):
        """Stacked per-stage states (None-free pytree; {} for stateless)."""
        return self._init_states_for(self.stage_defs, batch, max_len, mode)

    def _init_states_for(self, stage_defs, batch: int, max_len: int, mode: str):
        out = []
        for kinds, ng in stage_defs:
            one = {
                f"pos{i}": init_block_state(
                    kind, self.cfg, batch, max_len, mode, window=self.cfg.window
                )
                for i, kind in enumerate(kinds)
            }
            out.append(
                jax.tree.map(lambda a: jnp.broadcast_to(a[None], (ng,) + a.shape), one)
            )
        return out

    def prefill(self, params, batch: dict) -> Tuple[jnp.ndarray, list]:
        """Run the prompt, build caches.  Returns (last-token hidden, caches)."""
        tokens = batch["tokens"]
        B, S = tokens.shape
        max_len = batch.get("max_len", S)
        ctx = self._context(params, batch)
        x = self.embed(params, tokens)
        states = self.init_states(B, max_len, mode="prefill")
        x, states, _ = self._run_stack(
            self.stage_defs,
            params["stages"],
            x,
            states,
            q_pos=jnp.arange(S),
            ctx=ctx,
            mode="prefill",
            causal=True,
        )
        x = norm_apply(self.cfg.norm, params["final_norm"], x[:, -1:])
        return x[:, 0], states

    def decode_step(self, params, token, states, pos, *, in_place=False) -> Tuple[jnp.ndarray, list]:
        """One token for every sequence in the batch.  token: (B,1) int32;
        pos: () int32 current absolute position.  Returns (logits (B,V), states).

        Without ``in_place`` the layer scan reads ``states`` and writes the
        returned states whole, into a fresh buffer.  With it the states ride
        in the scan's carry and each layer writes only what its step changed
        (one cache slot), in place.  A program may not write its own input,
        so on states it was given XLA first copies them whole: that pays in
        a loop of steps, not for one."""
        x = self.embed(params, token)
        q_pos = pos[None] if jnp.ndim(pos) == 0 else pos
        x, states, _ = self._run_stack(
            self.stage_defs,
            params["stages"],
            x,
            states,
            q_pos=q_pos,
            ctx=None,
            mode="decode",
            causal=True,
            in_place=in_place,
        )
        x = norm_apply(self.cfg.norm, params["final_norm"], x)
        return self.logits(params, x)[:, 0], states
