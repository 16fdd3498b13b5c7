#!/usr/bin/env python3
"""Per-layer readings of one cell by the program's own names: device time
by named scope, device idle time by host span, and the metrics that read
them (``chipbench/layers.py``, ``chipbench/layer_metrics.py``).

    python chipbench/layer_report.py --workload qwen2_7b.decode --seed 7 --seconds 8

The cell runs as ``chipbench/run.py`` runs it (set-up, a measured window of
``--seconds``, a traced window of ``run.TRACE_SECONDS``), through the
closed-batch engine with each decode chunk run by ``serve.run_chunk``
inside the engine's ``decode_chunk`` span; a program without
``run_chunk`` runs the engine as it is.  No reference check is made.  The
last line of standard output is one JSON object: the new metrics, the
values the benchmark's readers give on the same trace, device seconds by
scope for each kind of program, idle seconds by span (overlap) and by
``chipbench.trace.reduce`` (midpoint), and the host time per decoded token
in the measured and the traced windows.

``--keep DIR`` writes the trace, the scope maps and the inputs of the
metrics there; ``--tiny`` serves a 2-layer, 256-wide Qwen2-shaped model
for a short traced window instead (the test trace of ``tests/bench``).
"""
from __future__ import annotations

import argparse
import collections
import gc
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
TINY_MODEL = {
    "name": "qwen2-tiny", "family": "dense", "n_layers": 2, "d_model": 256, "n_heads": 4,
    "n_kv_heads": 2, "d_ff": 512, "vocab_size": 1024, "qkv_bias": True,
    "rope_theta": 1000000.0, "group": ["attn"], "norm": "rmsnorm", "ffn": "swiglu",
    "param_dtype": "bfloat16", "compute_dtype": "bfloat16", "vocab_pad_multiple": 16}
TINY_TRAFFIC = {"loop": "closed", "batch": 4, "prompt_mix": [[8, 1], [16, 1]],
                "gen": 12, "max_len": 28}
TINY_TRACE_SECONDS = 0.015


def span_engine(base):
    """``base`` (the closed-batch engine) with each decode chunk run by
    ``serve.run_chunk``: the ``serve.dispatch`` and ``serve.wait`` spans
    inside the ``decode_chunk`` span, timed as the engine times it."""
    import numpy as np
    from jax.profiler import TraceAnnotation

    from chipbench.engines import closed_batch
    from repro.launch import serve

    if not hasattr(serve, "run_chunk"):
        return base

    class Engine(base):
        def _step(self) -> None:
            if self._live is None:
                self._start_job()
                return
            job, token, states, pos = self._live
            rem = self.traffic.gen - job.emitted
            ks = self.ks
            decision = None
            if rem < ks[-1]:
                k = max(1, min(int(self.tuner.exploit_point().get("k", 1)), rem))
                fn = (self.tuner.executable_for({"k": k}) if k in ks else None) or self.chunk_fns[k]
            else:
                with TraceAnnotation("router.begin"):
                    decision = self.router.begin(closed_batch.ROUTE)
                k = int(decision.point["k"])
                fn = decision.executable if decision.executable is not None else self.chunk_fns[k]
            with TraceAnnotation("decode_chunk"):
                tc = time.perf_counter()
                token, states, pos, toks = serve.run_chunk(fn, self.params, token, states, pos)
                t1 = time.perf_counter()
            if decision is not None:
                with TraceAnnotation("router.observe"):
                    self.router.observe(decision, (t1 - tc) / k)
            job.chunks.append(closed_batch.Chunk(
                k, tc, t1, job.prompts.shape[1] + job.emitted,
                decision is not None and decision.kind == "explore"))
            job.tokens.append(np.asarray(toks)[:, :, 0].T)
            job.emitted += k
            if job.emitted >= self.traffic.gen:
                job.finished = True
                self._live = None
            else:
                self._live = (job, token, states, pos)

    return Engine


def per_token_ms(chunks) -> dict:
    vals = [(c.t1 - c.t0) / c.k * 1e3 for _, c in chunks]
    if not vals:
        return {}
    return {"chunks": len(vals), "mean": statistics.fmean(vals), "median": statistics.median(vals)}


def report(cell: dict, seed: int, seconds: float, trace_seconds: float, peaks: dict,
           keep: str = None) -> dict:
    import jax

    from chipbench import catalog, layer_metrics, layers, record, run, trace, weights
    from chipbench.traffic import Traffic
    from repro.models import Model

    t_start = time.perf_counter()
    cfg = catalog.model_config(cell["config_file"])
    traffic = Traffic(cell["traffic_spec"], seed, cfg.vocab_size)
    shapes = jax.eval_shape(Model(cfg).init, jax.random.PRNGKey(0))
    params = weights.program_params(shapes, seed)
    base = catalog.engine(cell["engine"]).Engine
    eng = span_engine(base)(cfg, traffic, params,
                            extra={"arch": cell["config"], "batch": traffic.batch})
    del params
    eng.setup()
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    win = eng.run(seconds)
    router_stats = eng.router.stats()
    with tempfile.TemporaryDirectory(prefix="chipbench-layers-") as tdir:
        jax.profiler.start_trace(tdir, profiler_options=trace.profile_options())
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            traced = eng.run(trace_seconds, finish_job=False)
        jax.profiler.stop_trace()
        path = trace.find_xplane(tdir)
        tr = trace.load(path, run.SPANS + (trace.WINDOW_SPAN,))
        dev_win = trace.window(tr, trace.WINDOW_SPAN)
        summary = trace.reduce(tr, dev_win)
        tr_all, dims = layers.load(path, run.SPANS + layers.PROGRAM_SPANS + (trace.WINDOW_SPAN,))
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(path, os.path.join(keep, cell["name"] + ".xplane.pb"))
    programs = {**{f"prefill{P}": ex for P, ex in eng.prefill.items()},
                **{f"decode_k{k}": ex for k, ex in eng.chunk_fns.items()}}
    described = {name: layers.describe(ex.as_text()) for name, ex in programs.items()}
    scopes = layers.join(dims, described.values())
    rec = record.RunRecord(
        cell=cell, model_config=cell["config_file"]["model_config"], batch=traffic.batch,
        peaks=peaks, setup_s=setup_s, window=win, jobs=eng.jobs, router_stats=router_stats,
        prefill_modules=eng.prefill_modules, decode_modules=eng.decode_modules,
        traced=traced, trace=summary)
    eng.close()
    del eng
    gc.unfreeze()
    gc.collect()

    scope_s = layers.scope_time(tr_all, scopes)
    idle = layers.idle_by_span(tr_all, dev_win)
    positions = [c.pos + i for _, c in rec.window_chunks(traced) for i in range(c.k)]
    seqs = [j.prompts.shape[1] for j in rec.window_prefills(traced)]
    mc = rec.model_config
    metrics = layer_metrics.decode_metrics(
        scope_s, idle, modules=rec.decode_modules, cfg=mc, batch=rec.batch,
        positions=positions, peaks=peaks,
        chunks=layers.span_count(tr_all, "serve.dispatch", dev_win),
        routed=layers.span_count(tr_all, "tuner.begin", dev_win))
    metrics["attn_mfu.prefill"] = layer_metrics.attn_mfu(
        scope_s, rec.prefill_modules, mc, rec.batch, seqs, peaks)
    existing = {}
    for m in catalog.benchmark()["per_layer"]:
        if cell["name"] in m.get("workloads", [cell["name"]]):
            existing[m["name"]] = catalog.reader(m["name"])(rec)

    def by_module(modules):
        out = collections.Counter()
        for prog, by in scope_s.items():
            if prog.split("(", 1)[0] in modules:
                out.update(by)
        return dict(out.most_common())

    out = {
        "device": {"kind": jax.devices()[0].device_kind, "platform": jax.devices()[0].platform},
        "setup_s": setup_s,
        "metrics": metrics,
        "existing": existing,
        "busy_s": summary["busy_s"], "window_s": summary["window_s"],
        "module_s": summary["module_s"],
        "scope_s": {"decode": by_module(rec.decode_modules),
                    "prefill": by_module(rec.prefill_modules)},
        "idle_by_span": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        "idle_by_midpoint": trace.reduce(tr_all, dev_win)["idle_gaps"],
        "idle_gaps": summary["idle_gaps"],
        "device_ops": summary["device_ops"],
        "per_token_ms": {"measured": per_token_ms(rec.window_chunks()),
                         "traced": per_token_ms(rec.window_chunks(traced))},
        "unjoined_s": {p: by.get(layers.AMBIGUOUS, 0.0) + by.get("?", 0.0)
                       for p, by in scope_s.items()},
        "router_stats": {k: router_stats[k] for k in ("explores", "exploits", "drift_resets")},
    }
    if keep:
        # each program's shapes and scopes of the ops its module ran
        ran = collections.defaultdict(set)
        for prog, seen in dims.items():
            ran[prog.split("(", 1)[0]] |= seen.keys()
        kept = {name: [module] + [{op: v for op, v in d.items() if op in ran[module]}
                                  for d in maps]
                for name, (module, *maps) in described.items()}
        inputs = {"programs": kept, "window_ns": list(dev_win), "model_config": mc,
                  "batch": rec.batch, "decode_modules": sorted(rec.decode_modules),
                  "prefill_modules": sorted(rec.prefill_modules),
                  "positions": positions, "seqs": seqs}
        Path(keep, cell["name"] + ".json").write_text(
            json.dumps(inputs, separators=(",", ":"), sort_keys=True))
    return out


def tiny_cell(cell: dict) -> dict:
    return {**cell, "name": cell["config"] + ".tiny",
            "config_file": {**cell["config_file"], "model_config": dict(TINY_MODEL)},
            "traffic_spec": dict(TINY_TRAFFIC)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None, help="directory for the trace and scope maps")
    ap.add_argument("--tiny", action="store_true", help="a 2-layer, 256-wide model")
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)

    import jax

    from chipbench import catalog, run
    from chipbench.peaks import peaks_for
    from repro.compile_cache import use_compile_cache

    cell = catalog.cell(args.workload)
    if args.tiny:
        cell = tiny_cell(cell)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"JAX found no TPU (platform {devs[0].platform!r}): no result", file=sys.stderr)
        return 2
    use_compile_cache()
    out = report(cell, args.seed, args.seconds,
                 TINY_TRACE_SECONDS if args.tiny else run.TRACE_SECONDS,
                 peaks_for(devs[0].device_kind), keep=args.keep)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
