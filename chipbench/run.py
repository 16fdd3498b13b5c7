#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python chipbench/run.py --workload qwen2_7b.decode --seed 7 --seconds 30 --trace 0

One process holds the chip.  It makes the weights on the device from the
seed, compiles and warms every program the cell's traffic uses (set-up),
serves for ``--seconds`` and to the end of the job then in flight (the
window), and with ``--trace 1`` then serves a few seconds more under the
profiler (the traced window).  Once the windows
have closed and the peak memory has been read, it frees the program's state
and compares a sample of the served requests, drawn from the seed, with the
plain float32 reference.  The last line of standard output is the result as
JSON; the last lines of standard error are each number compared, beside its
limit.  Off a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
#: seconds the traced window serves, after the measured one
TRACE_SECONDS = 4.0
#: the gap reported when no request finished inside the window
NOTHING_COMPARED = 1e9
#: host spans the engine opens, read back from the trace
SPANS = ("new_job", "prefill", "decode_chunk", "router.begin", "router.observe")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts traces, compiles and compile-cache loads while open."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.count = 0

    def _listen(self, event, duration, **kwargs):
        if event in self.EVENTS:
            self.count += 1

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._listen)


def use_compile_cache() -> str:
    """JAX's persistent compilation cache, at a fixed path in the checkout:
    only a cell's first run there compiles."""
    import jax

    path = str(ROOT / ".bench_cache" / "jax")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def choose_sample(jobs: list, window: tuple, seed: int, n: int) -> list:
    """(job, row) pairs of requests finished inside the window to compare:
    one that ran each chunk size the window ran, one of the longest, then
    others drawn from the seed up to ``n`` in all, where that many finished."""
    import numpy as np

    done = [j for j in jobs if j.finished and j.chunks and j.chunks[-1].t1 <= window[1]]
    rng = np.random.default_rng([int(seed) % (1 << 64) & 0xFFFFFFFF, int(seed) % (1 << 64) >> 32, 3])
    chosen = []

    def take(job):
        row = int(rng.integers(job.prompts.shape[0]))
        if (job.index, row) not in {(j.index, r) for j, r in chosen}:
            chosen.append((job, row))

    seen = set()
    for j in done:
        ks = {c.k for c in j.chunks} - seen
        if ks:
            take(j)
            seen |= ks
    if done:
        take(max(done, key=lambda j: j.prompts.shape[1]))
    pool = [(j, r) for j in done for r in range(j.prompts.shape[0])]
    for i in rng.permutation(len(pool)):
        if len(chosen) >= n:
            break
        if pool[i] not in chosen:
            chosen.append(pool[i])
    return chosen


def check(cell: dict, seed: int, sample: list, *, control: bool = False) -> dict:
    """How far the served tokens' reference logits lie below the reference's
    best: the widest gap over every served token of the sample (and the
    float8 control's at the same positions, with ``control``)."""
    import numpy as np

    from chipbench import catalog
    from chipbench.reference import lm

    block = catalog.reference(cell["config_file"]["reference"])
    ref = lm.LM(cell["config_file"]["model_config"], block, seed)
    served, ctl = [], []
    for job, row in sample:
        g = lm.served_gaps(ref, job.prompts[row], job.served[row], control=control)
        served.append(g["served"])
        if control:
            ctl.append(g["control"])
    out = {"tokens": sum(g.size for g in served), "requests": len(sample),
           "per_request": [float(g.max()) for g in served]}
    for prefix, gaps in [("", served)] + ([("control_", ctl)] if control else []):
        # no finished request to compare is a failed run: a finite stand-in
        # keeps the result line valid JSON and fails any limit
        flat = np.concatenate(gaps) if gaps else np.full(1, NOTHING_COMPARED)
        out[prefix + "max_logit_gap"] = float(flat.max())
    return out


def verdict(limits: dict, gaps: dict, compiles: int, prefix: str = "") -> tuple:
    """``(correct, checks)``: each number the cell's limits name, read from
    ``gaps`` under ``prefix`` (``"control_"`` judges the control in the
    program's place), then the compiles inside the windows, which must be
    none."""
    checks = {name: {"value": gaps[prefix + name], "limit": limit}
              for name, limit in limits.items()}
    checks["window_compiles"] = {"value": compiles, "limit": 0}
    correct = bool(gaps["requests"]) and all(c["value"] <= c["limit"] for c in checks.values())
    return correct, checks


def measure(cell: dict, seed: int, seconds: float, traced: bool, *, t_start: float,
            peaks: dict, control: bool = False) -> dict:
    """Set up, serve, read, free, compare: the whole run of one cell, on
    whatever device JAX gives (``main`` checks that it is the chip and
    looks up its ``peaks``)."""
    import jax

    from chipbench import catalog, record, trace, weights
    from chipbench.traffic import Traffic
    from repro.models import Model

    device = jax.devices()[0]
    cfg = catalog.model_config(cell["config_file"])
    traffic = Traffic(cell["traffic_spec"], seed, cfg.vocab_size)
    shapes = jax.eval_shape(Model(cfg).init, jax.random.PRNGKey(0))
    params = weights.program_params(shapes, seed)
    eng = catalog.engine(cell["engine"]).Engine(
        cfg, traffic, params, extra={"arch": cell["config"], "batch": traffic.batch})
    del params
    eng.setup()
    # what set-up left on the heap (compiles above all) is never scanned by
    # a garbage collection inside the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.2f} s")

    with CompileCounter() as compiles:
        win = eng.run(seconds)
        router_stats = eng.router.stats()
        traced_win = summary = None
        if traced:
            with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tdir:
                jax.profiler.start_trace(tdir, profiler_options=trace.profile_options())
                with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                    traced_win = eng.run(TRACE_SECONDS, finish_job=False)
                jax.profiler.stop_trace()
                tr = trace.load(trace.find_xplane(tdir), SPANS + (trace.WINDOW_SPAN,))
            summary = trace.reduce(tr, trace.window(tr, trace.WINDOW_SPAN))
    stats = device.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))

    run = record.RunRecord(
        cell=cell, model_config=cell["config_file"]["model_config"], batch=traffic.batch,
        peaks=peaks, setup_s=setup_s, window=win, jobs=eng.jobs,
        router_stats=router_stats, prefill_modules=eng.prefill_modules,
        decode_modules=eng.decode_modules, traced=traced_win, trace=summary)
    sample = choose_sample(eng.jobs, win, seed, int(cell["check"]["sample"]))
    eng.close()
    del eng
    gc.unfreeze()
    gc.collect()

    t0 = time.perf_counter()
    gaps = check(cell, seed, sample, control=control)
    log(f"reference over {gaps['requests']} requests, {gaps['tokens']} served tokens: "
        f"{time.perf_counter() - t0:.2f} s; widest gap {gaps['max_logit_gap']!r}")
    limits = cell["check"]["limits"]
    correct, checks = verdict(limits, gaps, compiles.count)

    metrics = {}
    for m in catalog.metrics_for(cell["name"], traced):
        v = catalog.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    attempted = traffic.batch * len(run.window_prefills())
    devs = jax.devices()
    result = {
        "correct": correct,
        "attempted": attempted,
        "failed": 0,
        "metrics": metrics,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(devs), "memory_peak_bytes": mem_peak},
    }
    if summary is not None:
        result["device"]["busy_s"] = summary["busy_s"]
        result["device"]["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = checks
    out = {"result": result, "gaps": gaps, "run": run, "router_stats": router_stats}
    if control:
        out["control_correct"], out["control_checks"] = verdict(
            limits, gaps, compiles.count, prefix="control_")
    return out


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_cache" / "tpu_logs"))

    import jax

    from chipbench import catalog
    from chipbench.peaks import peaks_for

    cell = catalog.cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"JAX found no TPU (platform {devs[0].platform!r}): no result")
        return 2
    if len(devs) < cell["chips"]:
        log(f"{cell['chips']} chips asked for, {len(devs)} present: no result")
        return 2
    peaks = peaks_for(devs[0].device_kind)
    use_compile_cache()
    out = measure(cell, args.seed, args.seconds, bool(args.trace), t_start=t_start, peaks=peaks)
    res = out["result"]
    log(f"window: {res['attempted']} requests; router {out['router_stats']['explores']} explore / "
        f"{out['router_stats']['exploits']} exploit chunks, "
        f"{out['router_stats']['drift_resets']} drift re-tunes")
    for name, c in res["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
