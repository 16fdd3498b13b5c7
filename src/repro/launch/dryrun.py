import os


def _ensure_host_platform_devices(default: int = 512) -> None:
    """Set the host-platform device count, PRESERVING any other XLA_FLAGS the
    user (or the flag-tuning layer) already exported — this module used to
    clobber the whole variable.  Only an existing
    ``--xla_force_host_platform_device_count`` token is replaced; everything
    else is kept verbatim.  Must run before any jax import (jax locks the
    device count at first init).  Tests override via REPRO_DRYRUN_DEVICES."""
    n = int(os.environ.get("REPRO_DRYRUN_DEVICES") or default)
    kept = [
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    ]
    kept.append(f"--xla_force_host_platform_device_count={n}")
    os.environ["XLA_FLAGS"] = " ".join(kept)


_ensure_host_platform_devices()

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production mesh; record memory analysis, cost analysis and the collective
schedule for the roofline (EXPERIMENTS.md §Dry-run / §Roofline).

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod --out results/
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.core.costs import TPU_V5E, RooflineTerms
from repro.launch import costing
from repro.launch.input_specs import input_specs
from repro.launch.mesh import default_rules, make_mesh, make_production_mesh
from repro.models import ExecConfig, Model
from repro.optim import AdamW
from repro.parallel.api import sharding_context
from repro.parallel.sharding import (
    batch_wanted,
    param_wanted,
    state_wanted,
    tree_shardings,
    replicated,
)
from repro.train import make_train_step
from jax.sharding import NamedSharding, PartitionSpec


def _opt_wanted(path, ndim):
    if path.startswith(("m/", "v/")):
        return param_wanted(path[2:], ndim)
    return ()


def _batch_wanted(path, ndim):
    name = path.split("/")[-1]
    return batch_wanted(name, ndim)


def default_exec(cfg, shape_kind: str, overrides: dict | None = None) -> ExecConfig:
    """Baseline execution config (the paper-faithful starting point;
    ``--set`` overrides fields via ``overrides``)."""
    kw = dict(
        scan_layers=True,
        scan_unroll=1,
        remat="full" if shape_kind == "train" else "none",
        logits_chunk=0,
        rec_chunk=128,
        rec_unroll=True,  # exact cost_analysis (no nested while)
    )
    kw.update(overrides or {})
    return ExecConfig(**kw)


def build_step(model, shape_kind: str, mesh, rules, *, microbatches: int = 1,
               logits_chunk: int = 0):
    """Returns (fn, example_args, in_shardings, out_shardings, donate)."""
    cfg = model.cfg
    params_spec = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params_sh = tree_shardings(mesh, rules, params_spec, param_wanted)

    if shape_kind == "train":
        opt = AdamW(lr=3e-4, state_dtype=cfg.param_dtype)
        opt_spec = jax.eval_shape(opt.init, params_spec)
        opt_sh = tree_shardings(mesh, rules, opt_spec, _opt_wanted)
        step = make_train_step(model, opt, microbatches=microbatches, logits_chunk=logits_chunk)
        shape = None  # filled by caller
        return step, (params_spec, opt_spec), (params_sh, opt_sh), None

    if shape_kind == "prefill":
        def prefill(params, batch):
            hidden, states = model.prefill(params, batch)
            logits = model.logits(params, hidden[:, None])[:, 0]
            return logits, states

        return prefill, (params_spec,), (params_sh,), None

    def decode(params, token, states, pos):
        return model.decode_step(params, token, states, pos)

    return decode, (params_spec,), (params_sh,), None


def build_cell_program(cfg, exec_cfg, shape_name, mesh, rules, *, microbatches=1):
    """(fn, args, in_shardings, out_shardings, donate) for one cell."""
    from repro.parallel.api import logical_spec

    shape = configs.SHAPES[shape_name]
    model = Model(cfg, exec_cfg)
    specs = input_specs(model, shape_name)
    params_spec = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params_sh = tree_shardings(mesh, rules, params_spec, param_wanted)

    if shape.kind == "train":
        opt = AdamW(lr=3e-4, state_dtype=cfg.param_dtype)
        opt_spec = jax.eval_shape(opt.init, params_spec)
        opt_sh = tree_shardings(mesh, rules, opt_spec, _opt_wanted)
        batch_sh = tree_shardings(mesh, rules, specs["batch"], _batch_wanted)
        fn = make_train_step(
            model, opt, microbatches=microbatches, logits_chunk=exec_cfg.logits_chunk
        )
        args = (params_spec, opt_spec, specs["batch"])
        in_sh = (params_sh, opt_sh, batch_sh)
        out_spec = jax.eval_shape(fn, *args)
        out_sh = (params_sh, opt_sh, replicated(mesh, out_spec[2]))
        donate = (0, 1)
    elif shape.kind == "prefill":
        def fn(params, batch):
            hidden, states = model.prefill(params, batch)
            logits = model.logits(params, hidden[:, None])[:, 0]
            return logits, states

        batch_sh = tree_shardings(mesh, rules, specs["batch"], _batch_wanted)
        args = (params_spec, specs["batch"])
        in_sh = (params_sh, batch_sh)
        out_spec = jax.eval_shape(fn, *args)
        logits_sh = NamedSharding(mesh, logical_spec(mesh, rules, out_spec[0].shape, ("dp", "tp")))
        states_sh = tree_shardings(
            mesh, rules, out_spec[1], lambda p, sh: state_wanted(p.split("/", 1)[-1], sh, tp_size=mesh.shape.get("model", 0))
        )
        out_sh = (logits_sh, states_sh)
        donate = ()
    else:  # decode
        def fn(params, token, states, pos):
            return model.decode_step(params, token, states, pos)

        token_sh = NamedSharding(mesh, logical_spec(mesh, rules, specs["token"].shape, ("dp", None)))
        states_sh = tree_shardings(
            mesh, rules, specs["states"], lambda p, sh: state_wanted(p.split("/", 1)[-1], sh, tp_size=mesh.shape.get("model", 0))
        )
        pos_sh = NamedSharding(mesh, PartitionSpec())
        args = (params_spec, specs["token"], specs["states"], specs["pos"])
        in_sh = (params_sh, token_sh, states_sh, pos_sh)
        out_spec = jax.eval_shape(fn, *args)
        logits_sh = NamedSharding(mesh, logical_spec(mesh, rules, out_spec[0].shape, ("dp", "tp")))
        out_sh = (logits_sh, states_sh)
        donate = (2,)
    return fn, args, in_sh, out_sh, donate


def _variant_cfg(cfg, k_groups: int, enc_layers: int | None = None):
    kw = dict(
        n_groups=k_groups,
        n_layers=len(cfg.group) * k_groups + len(cfg.tail),
    )
    if enc_layers is not None:
        kw["enc_layers"] = enc_layers
    return dataclasses.replace(cfg, **kw)


def _lower_cost(cfg, exec_cfg, shape_name, mesh, rules, microbatches):
    fn, args, in_sh, out_sh, donate = build_cell_program(
        cfg, exec_cfg, shape_name, mesh, rules, microbatches=microbatches
    )
    with sharding_context(mesh, rules):
        lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                          donate_argnums=donate).lower(*args)
    return costing.measure(lowered.compile())


def cost_by_delta(cfg, exec_cfg, shape_name, mesh, rules, microbatches) -> costing.CostTerms:
    """Exact-in-context while-loop-free cost: lower fully-unrolled variants
    with 2 and 4 layer groups, difference them for the per-group cost, and
    extrapolate to the real depth (EXPERIMENTS.md §Dry-run methodology).
    cost_analysis counts while bodies once, so unrolled variants are the only
    faithful accounting; deltas keep sharding context identical."""
    ec = dataclasses.replace(exec_cfg, scan_unroll=1_000_000, rec_unroll=True)
    ng = cfg.n_groups
    enc = cfg.enc_layers
    if ng <= 4 and enc <= 4:
        return _lower_cost(cfg, ec, shape_name, mesh, rules, microbatches)
    enc_small = min(enc, 2) if enc else 0
    c2 = _lower_cost(
        _variant_cfg(cfg, 2, enc_small or None), ec, shape_name, mesh, rules, microbatches
    )
    c4 = _lower_cost(
        _variant_cfg(cfg, 4, enc_small or None), ec, shape_name, mesh, rules, microbatches
    )
    per_group = (c4 + c2.scaled(-1.0)).scaled(0.5)
    total = c2 + per_group.scaled(ng - 2)
    if enc > 2:
        shape = configs.SHAPES[shape_name]
        if shape.kind != "decode":  # decode never runs the encoder
            e4 = _lower_cost(
                _variant_cfg(cfg, 2, 4), ec, shape_name, mesh, rules, microbatches
            )
            per_enc = (e4 + c2.scaled(-1.0)).scaled(0.5)
            total = total + per_enc.scaled(enc - 2)
    return total


def run_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    tiny: bool = False,
    mesh_spec=None,
    exec_overrides: dict | None = None,
    cfg_overrides: dict | None = None,
    microbatches: int = 1,
    fsdp: bool = True,
    sp: bool = False,
    probes: bool = True,
    verbose: bool = True,
    compiler_options: dict | None = None,
) -> dict:
    t0 = time.time()
    cfg = configs.get_tiny(arch) if tiny else configs.get(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = configs.SHAPES[shape_name]
    if shape_name == "long_500k" and not cfg.subquadratic and not tiny:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": "full-attention arch; long_500k runs for SSM/hybrid only (DESIGN §5)"}

    exec_cfg = default_exec(cfg, shape.kind, exec_overrides)
    model = Model(cfg, exec_cfg)
    if mesh_spec is not None:
        mesh = make_mesh(*mesh_spec)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    rules = default_rules(mesh, fsdp=fsdp, sp=sp)
    chips = int(mesh.size)

    # 1) the production program (scan-over-layers): proves compile + memory
    fn, args, in_sh, out_sh, donate = build_cell_program(
        cfg, exec_cfg, shape_name, mesh, rules, microbatches=microbatches
    )
    with sharding_context(mesh, rules):
        lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                          donate_argnums=donate).lower(*args)
        # tuned XLA flags are applied per-compile (launch.spaces.XLA_PRESETS)
        # — never via the import-time XLA_FLAGS env hack
        if compiler_options:
            compiled = lowered.compile(compiler_options=dict(compiler_options))
        else:
            compiled = lowered.compile()
    mem = compiled.memory_analysis()
    step_cost = costing.measure(compiled)

    # 2) cost accounting via unrolled delta variants (exact; see cost_by_delta)
    if probes:
        total = cost_by_delta(cfg, exec_cfg, shape_name, mesh, rules, microbatches)
    else:
        total = step_cost

    hw = TPU_V5E
    terms = RooflineTerms(
        compute_s=total.flops / hw.peak_flops,
        memory_s=total.bytes_accessed / hw.hbm_bw,
        collective_s=total.coll_bytes / (hw.ici_bw * 4),
        flops=total.flops,
        bytes_accessed=total.bytes_accessed,
        coll_bytes=total.coll_bytes,
        chips=chips,
        hw=hw,
    )

    # model flops (6ND train / 2ND inference; N_active for MoE)
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    model_flops_global = (6 if shape.kind == "train" else 2) * n_active * tokens
    model_flops_per_chip = model_flops_global / chips

    result = {
        "arch": arch,
        "shape": shape_name,
        "status": "ok",
        "mesh": dict(zip(mesh.axis_names, (int(s) for s in mesh.devices.shape))),
        "chips": chips,
        "exec": dataclasses.asdict(exec_cfg),
        "fsdp": fsdp,
        "sp": sp,
        "microbatches": microbatches,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": mem.peak_memory_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "cost_reported": step_cost.as_dict(),
        "cost_total_per_chip": total.as_dict(),
        "roofline": terms.as_dict(),
        "model_flops_per_chip": model_flops_per_chip,
        "useful_flops_ratio": model_flops_per_chip / max(total.flops, 1.0),
        "params": cfg.param_count(),
        "active_params": n_active,
        "elapsed_s": round(time.time() - t0, 1),
    }
    if verbose:
        dom = terms.dominant
        print(
            f"[dryrun] {arch} × {shape_name} ({'multi' if multi_pod else 'single'}-pod, "
            f"{chips} chips): compile OK in {result['elapsed_s']}s\n"
            f"  mem/chip: args {mem.argument_size_in_bytes/1e9:.2f} GB, "
            f"temp {mem.temp_size_in_bytes/1e9:.2f} GB, "
            f"peak {result['memory']['peak_bytes']/1e9:.2f} GB\n"
            f"  roofline/chip: compute {terms.compute_s*1e3:.2f} ms | memory "
            f"{terms.memory_s*1e3:.2f} ms | collective {terms.collective_s*1e3:.2f} ms "
            f"-> {dom}-bound\n"
            f"  useful-flops ratio (6ND / HLO): {result['useful_flops_ratio']:.2f}"
        )
    return result


def _tuned_point(db, arch: str, shape_name: str, mesh_spec) -> dict | None:
    """Stored launch point for this cell, trying the dryrun-mode key first
    and falling back to the deterministic model-mode key (the records CI's
    `pretune --launch` commits)."""
    from repro.launch.spaces import launch_key, launch_space

    cfg = configs.get(arch)
    shape = configs.SHAPES[shape_name]
    if mesh_spec is not None:
        n = 1
        for s in mesh_spec[0]:
            n *= int(s)
    else:
        n = jax.device_count()
    space = launch_space(cfg, shape, n)
    for mode in ("dryrun", "model"):
        rec = db.get(launch_key(arch, shape, n, space, mode=mode))
        if rec is not None:
            return dict(rec.point)
    return None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None, choices=list(configs.SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--sp", action="store_true")
    ap.add_argument("--set", nargs="*", default=[], help="exec overrides k=v")
    ap.add_argument("--out", type=str, default=None, help="append JSONL here")
    ap.add_argument(
        "--mesh", type=str, default=None,
        help="override mesh shape, e.g. '4,4' (data,model) or '2,2,4' (pod,data,model)",
    )
    ap.add_argument(
        "--tune", action="store_true",
        help="apply each cell's tuned launch point from --db (launch.spaces) "
             "and report tuned vs default end-to-end step estimate",
    )
    ap.add_argument("--db", type=str, default=None,
                    help="tuning DB holding launch/<arch> records (with --tune)")
    args = ap.parse_args()

    mesh_spec = None
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split(","))
        axes = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
        mesh_spec = (dims, axes)

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=")
        overrides[k] = (
            v if k == "remat" else (v == "True" if v in ("True", "False") else int(v))
        )

    cells = []
    if args.all:
        for a, s, runnable in configs.cells(include_skips=True):
            cells.append((a, s))
    else:
        cells.append((args.arch, args.shape))

    tuned_db = None
    if args.tune:
        if not args.db:
            raise SystemExit("--tune needs --db <tuning db with launch records>")
        from repro.tuning import TuningDB

        tuned_db = TuningDB(args.db)

    def _bound(r):
        rf = r.get("roofline") or {}
        return max(rf.get("compute_s", 0), rf.get("memory_s", 0),
                   rf.get("collective_s", 0))

    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    results = []
    for arch, shape in cells:
        for mp in meshes:
            cell_kw = dict(
                multi_pod=mp, tiny=args.tiny, mesh_spec=mesh_spec,
                exec_overrides=dict(overrides), microbatches=args.microbatches,
                fsdp=not args.no_fsdp, sp=args.sp, probes=not args.no_probes,
            )
            try:
                if tuned_db is not None:
                    point = _tuned_point(tuned_db, arch, shape, mesh_spec)
                    if point is None:
                        r = run_cell(arch, shape, **cell_kw)
                        r["launch_tuned"] = False
                    else:
                        from repro.launch.spaces import apply_launch_point

                        n = (point["dp"] * point["tp"])
                        tuned_kw = dict(cell_kw)
                        tuned_kw.update(apply_launch_point(
                            point, n, jax.default_backend()
                        ))
                        tuned_kw["exec_overrides"] = dict(
                            overrides, **tuned_kw.pop("exec_overrides", {})
                        )
                        r = run_cell(arch, shape, **tuned_kw)
                        base = run_cell(arch, shape, **dict(cell_kw, verbose=False))
                        r["launch_tuned"] = True
                        r["launch_point"] = dict(point)
                        r["step_bound_s"] = _bound(r)
                        r["default_step_bound_s"] = _bound(base)
                        if r["status"] == "ok" and base["status"] == "ok":
                            print(
                                f"[dryrun --tune] {arch} × {shape}: tuned "
                                f"{r['step_bound_s']*1e3:.2f} ms vs default "
                                f"{r['default_step_bound_s']*1e3:.2f} ms per step"
                            )
                else:
                    r = run_cell(arch, shape, **cell_kw)
            except Exception as e:
                traceback.print_exc()
                r = {"arch": arch, "shape": shape, "multi_pod": mp,
                     "status": "error", "error": repr(e)}
            r["multi_pod"] = mp
            results.append(r)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(r) + "\n")
    bad = [r for r in results if r["status"] == "error"]
    print(f"\n[dryrun] {len(results)} cells: "
          f"{sum(r['status']=='ok' for r in results)} ok, "
          f"{sum(r['status']=='skipped' for r in results)} skipped, {len(bad)} errors")
    if bad:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
