"""Offline pre-tuning sweep — fill the tuning DB before anyone pays online.

    PYTHONPATH=src python -m repro.tuning.pretune --db tuned/cpu.json --smoke
    PYTHONPATH=src python -m repro.tuning.pretune --db tuned/cpu.json \
        --kernel matmul --kernel flash_attention
    PYTHONPATH=src python -m repro.tuning.pretune --db tuned/cpu.json --list
    PYTHONPATH=src python -m repro.tuning.pretune --db tuned/serve.json \
        --only 'matmul/128*'
    PYTHONPATH=src python -m repro.tune pretune --db tuned/shard0.json \
        --smoke --shard 0/4          # fleet worker 0 of 4

(``python -m repro.tune pretune`` is the same command behind the umbrella
CLI, which also provides ``db merge`` / ``db list`` / ``db diff``.)

**Fleet sharding**: ``--shard i/n`` keeps only the contexts whose stable
fingerprint hash lands in shard ``i`` — n workers running the same command
with shards 0..n-1 partition the grid exactly, with zero coordination and
no shared filesystem; each writes its own ``--db`` and
``python -m repro.tune db merge`` folds them.  ``--cost analytic`` swaps
wall-clock measurement for the candidate's deterministic roofline bound and
``--no-warm-start`` removes sweep-order dependence, together making a
sharded sweep bit-reproduce the unsharded one (the CI equivalence lane).

**Crash resume**: every sweep writes a write-ahead journal next to the DB
(``<db>.journal`` — one fsynced JSONL event per case: ``start`` before
measurement, ``commit``/``failed`` after).  A worker killed mid-sweep
restarts with ``--resume`` and re-measures nothing already completed; the
journal's committed records also reconstruct the DB if the kill tore the
final save, and ``python -m repro.tune db merge`` accepts partial journals
as sources directly.

Sweeps the registered (kernel, shape) grid, runs the PATSMA search per
context, and commits every record atomically.  Each context's candidate
rounds are AOT-compiled concurrently (``--jobs`` threads; measurement stays
serial) through the process-wide executable cache, so revisited candidates
never recompile.  ``--measure adaptive`` (the default) races each round's
candidates — dominated ones are culled after a single repetition and
roofline-hopeless ones skip measurement — while ``--measure fixed`` keeps
the classic ``RuntimeCost`` fixed-repeat loop for trajectory-pinned
reproduction; the run summary reports repetitions spent, culls, and prunes.
A case that commits no record (every candidate failed) makes the sweep exit
1.  Kernels run in Pallas interpret mode on the CPU backend and compiled on
every other platform.  The committed ``tuned/cpu.json`` snapshot is what the
test suite and CI replay: the suite's kernel dispatches become exact
fingerprint hits, so they skip straight to the stored best with zero
re-measurement.  On a TPU host the same command (without ``--smoke``)
produces the production snapshot for that device kind.

``--list`` prints the registered grid with each case's DB status (exact hit
/ warm neighbor / cold, plus the stored record's search strategy) without
tuning anything, and ``--only <glob>`` restricts a sweep to matching cases —
together they are how a serving deployment seeds exactly the router contexts
its traffic will touch, without sweeping the whole grid.  ``--strategy
csa+nm`` swaps the per-context search for the paper's CSA→NM hybrid pipeline
(or any :func:`repro.core.strategy.make_strategy` spec) at the same total
measurement budget; the spec is stamped on every committed record.
"""
from __future__ import annotations

import argparse
import fnmatch
import sys
import time


def _cases(smoke: bool, abstract: bool = False):
    """(kernel name, case label, thunk building the call args) grid.  Thunks
    defer array construction so filtering never materializes unused inputs.
    ``abstract=True`` yields ``jax.ShapeDtypeStruct`` stand-ins — enough for
    fingerprints and search spaces (both read only shape/dtype), so
    ``--list`` stays metadata-only instead of allocating the whole grid."""
    import jax
    import jax.numpy as jnp

    if abstract:
        def rnd(seed, shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype)

        def filled(value, shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype)
    else:
        def rnd(seed, shape, dtype=jnp.float32):
            return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)

        def filled(value, shape, dtype=jnp.float32):
            return jnp.full(shape, value, dtype)

    if smoke:
        mm_shapes = [(64, 64, 64), (128, 128, 128)]
        fa_shapes = [(1, 2, 2, 64, 16)]
        da_shapes = [(2, 4, 2, 128, 16)]
        ls_shapes = [(2, 64, 32)]
    else:
        mm_shapes = [(128,) * 3, (256,) * 3, (512, 512, 256)]
        fa_shapes = [(1, 2, 2, 64, 16), (1, 4, 2, 128, 32), (2, 4, 4, 256, 32)]
        da_shapes = [(2, 4, 2, 128, 16), (4, 8, 2, 512, 32)]
        ls_shapes = [(2, 64, 32), (2, 256, 64)]

    cases = []
    for m, n, k in mm_shapes:
        cases.append(
            ("matmul", f"{m}x{n}x{k}",
             lambda m=m, n=n, k=k: (rnd(0, (m, k)), rnd(1, (k, n))))
        )
    for b, h, kh, s, hd in fa_shapes:
        cases.append(
            (
                "flash_attention",
                f"b{b}h{h}kh{kh}s{s}d{hd}",
                lambda b=b, h=h, kh=kh, s=s, hd=hd: (
                    rnd(0, (b, s, h, hd)),
                    rnd(1, (b, kh, s, hd)),
                    rnd(2, (b, kh, s, hd)),
                ),
            )
        )
    for b, h, kh, s, hd in da_shapes:
        cases.append(
            (
                "decode_attention",
                f"b{b}h{h}kh{kh}s{s}d{hd}",
                lambda b=b, h=h, kh=kh, s=s, hd=hd: (
                    rnd(0, (b, h, hd)),
                    rnd(1, (b, kh, s, hd)),
                    rnd(2, (b, kh, s, hd)),
                    filled(1, (b, s), jnp.int32),
                ),
            )
        )
    for b, t, d in ls_shapes:
        cases.append(
            (
                "lru_scan",
                f"b{b}t{t}d{d}",
                lambda b=b, t=t, d=d: (
                    filled(0.9, (b, t, d)),
                    rnd(1, (b, t, d)),
                    rnd(2, (b, d)),
                ),
            )
        )
    return cases


def _case_key(name: str, abstract_args, interpret: bool):
    """The context fingerprint :func:`tune_call` would compute for this case
    — built from ``ShapeDtypeStruct`` stand-ins (signatures and search
    spaces read only shape/dtype), so shard assignment never materializes
    the grid."""
    from repro.kernels.autotuned import get_spec
    from repro.tuning import make_key

    spec = get_spec(name)
    space = spec.space(*abstract_args)
    return make_key(name, args=abstract_args, space=space,
                    extra={"interpret": bool(interpret)})


def _shard_filter(cases, smoke, wanted, only, shard, interpret: bool):
    """Keep the cases whose fingerprint lands in ``shard`` = (index, num).
    Assignment hashes the full context key (:meth:`TuningKey.shard`), so
    every fleet worker computes the same partition with no coordination."""
    from repro.tuning.fleet import in_shard

    index, num = shard
    abstract = {
        (n, label): build
        for n, label, build in _selected(_cases(smoke, abstract=True), wanted, only)
    }
    kept = []
    for name, label, build in cases:
        key = _case_key(name, abstract[(name, label)](), interpret=interpret)
        if in_shard(key, index, num):
            kept.append((name, label, build))
    return kept


def _analytic_cost_fn():
    """Deterministic stand-in for wall-clock measurement: the candidate's
    roofline lower bound on a TPU v5e, whatever the host
    (:func:`repro.core.costs.roofline_terms` over its compiled HLO).
    Identical inputs give identical costs on every host and
    every run, which is what the fleet's shard-equivalence contract needs —
    a sharded sweep and an unsharded sweep must land on the same best
    points.  Candidates whose HLO defeats cost analysis fall back to a
    constant (still deterministic); relative quality between such ties is
    then decided by the search trajectory, which is equally deterministic."""
    from repro.core import TPU_V5E, roofline_terms

    def cost(ex, *args):
        try:
            b = float(roofline_terms(ex, chips=1, hw=TPU_V5E).bound_s)
        except Exception:
            b = 0.0
        return b if b > 0.0 else 1.0

    return cost


def _selected(cases, wanted, only):
    """Filter the grid by --kernel names and --only globs (case ids match as
    ``kernel`` or ``kernel/label``)."""
    out = []
    for name, label, build in cases:
        if wanted is not None and name not in wanted:
            continue
        case_id = f"{name}/{label}"
        if only and not any(
            fnmatch.fnmatch(case_id, pat) or fnmatch.fnmatch(name, pat)
            for pat in only
        ):
            continue
        out.append((name, label, build))
    return out


def _space_size_str(space) -> str:
    """`size=raw/feasible` — the resolved product-space size and what is left
    after the validity predicates prune (equal when unconstrained), so
    operators can see whether a sweep is tractable before launching it."""
    raw = space.size()
    if raw is None:
        return "size=∞"
    feas = space.constrained_size()
    return f"size={raw}/{feas if feas is not None else '?'}"


def _list_grid(cases, db, interpret: bool) -> int:
    """Print each case with its resolved space size (raw/constrained) and DB
    status: exact hit, warm neighbor, or cold."""
    from repro.kernels.autotuned import get_spec
    from repro.tuning import make_key

    for name, label, build in cases:
        call_args = build()
        spec = get_spec(name)
        space = spec.space(*call_args)
        key = make_key(name, args=call_args, space=space,
                       extra={"interpret": bool(interpret)})
        rec, exact = db.lookup(key)
        case_id = f"{name}/{label}"
        case_id = f"{case_id:<28} {_space_size_str(space):<14}"
        if exact:
            # same convention as the run summary: the default CSA search is
            # not news, only a non-default strategy earns the column
            strat = (f" strategy={rec.strategy}"
                     if rec.strategy and rec.strategy != "csa" else "")
            print(f"  {case_id:<42} HIT   best={rec.point} "
                  f"cost={rec.cost * 1e3:.2f}ms source={rec.source}{strat}")
        elif rec is not None and key.distance(rec.key) != float("inf"):
            print(f"  {case_id:<42} warm  neighbor={rec.point} "
                  f"(shapes {rec.key.shapes()})")
        else:
            print(f"  {case_id:<42} cold")
    return 0


def _launch_main(args, db, *, max_iter: int) -> int:
    """The ``--launch`` family: sweep launch-level (arch, shape) contexts
    through :func:`repro.launch.spaces.tune_launch` with the same journal /
    shard / list / resume machinery as the kernel grid."""
    import os

    from repro import configs, obs
    from repro.launch.spaces import (
        launch_cases,
        launch_key,
        launch_space,
        tune_launch,
    )
    from repro.tuning import RunJournal

    n_devices = args.devices or int(os.environ.get("REPRO_DRYRUN_DEVICES") or 8)
    mode = "model" if args.cost == "analytic" else "dryrun"
    cases = launch_cases(smoke=args.smoke)
    if args.only:
        cases = [
            (a, s) for a, s in cases
            if any(fnmatch.fnmatch(f"launch/{a}/{s}", pat)
                   or fnmatch.fnmatch(a, pat) for pat in args.only)
        ]
    if not cases:
        print("pretune: no launch cases match the given filters", file=sys.stderr)
        return 2

    def case_key(arch, shape_name):
        cfg = configs.get(arch)
        shape = configs.SHAPES[shape_name]
        space = launch_space(cfg, shape, n_devices)
        return launch_key(arch, shape, n_devices, space, mode=mode), space

    if args.shard is not None:
        from repro.tuning.fleet import in_shard, parse_shard

        index, num = parse_shard(args.shard)
        total = len(cases)
        cases = [
            (a, s) for a, s in cases if in_shard(case_key(a, s)[0], index, num)
        ]
        print(f"pretune: shard {index}/{num}: {len(cases)}/{total} launch cases")
        if not cases:
            db.save()
            return 0

    if args.list_grid:
        for arch, shape_name in cases:
            key, space = case_key(arch, shape_name)
            rec, exact = db.lookup(key)
            case_id = f"launch/{arch}/{shape_name}"
            case_id = f"{case_id:<40} {_space_size_str(space):<16}"
            if exact:
                print(f"  {case_id} HIT   best={rec.point} cost={rec.cost:.4g}s "
                      f"source={rec.source}")
            else:
                print(f"  {case_id} cold  devices={n_devices} mode={mode}")
        return 0

    jpath = RunJournal.path_for(args.db)
    done_keys: set = set()
    if args.resume:
        journal = RunJournal(jpath)
        s = journal.summary()
        done_keys = set(s["committed"]) | set(s["failed"])
        if s["committed"]:
            db.merge(journal.to_db())
        journal.resume()
        print(f"pretune: resume from {jpath}: skipping {len(done_keys)} "
              f"completed launch cases")
    else:
        if os.path.exists(jpath):
            os.remove(jpath)
        journal = RunJournal(jpath)

    n_done = 0
    n_failed = 0
    t_all = time.perf_counter()
    totals = {"measured": 0, "pruned": 0}
    sweep_span = obs.span("pretune", cases=len(cases), family="launch")
    sweep_span.__enter__()
    try:
        for arch, shape_name in cases:
            key, space = case_key(arch, shape_name)
            if key.encode() in done_keys:
                continue
            t0 = time.perf_counter()
            stats: dict = {}
            journal.start(key)
            rec = tune_launch(
                arch,
                shape_name,
                n_devices,
                db=db,
                mode=mode,
                num_opt=args.num_opt,
                max_iter=max_iter,
                seed=args.seed,
                search=args.strategy,
                warm_start=not args.no_warm_start,
                source="pretune",
                stats=stats,
            )
            dt = time.perf_counter() - t0
            totals["measured"] += int(stats.get("measured", 0))
            totals["pruned"] += int(stats.get("pruned", 0))
            if rec is None:
                journal.failed(key, "every candidate failed")
                print(f"  launch/{arch}/{shape_name}: every candidate failed; "
                      f"nothing stored ({dt:.1f}s)", file=sys.stderr)
                n_failed += 1
                continue
            journal.commit(key, rec)
            sz = _space_size_str(space)
            replay = " (replayed)" if stats.get("replayed") else ""
            print(
                f"  launch/{arch}/{shape_name}: best={rec.point} "
                f"cost={rec.cost:.4g}s {sz} measured={stats.get('measured', 0)} "
                f"pruned={stats.get('pruned', 0)}{replay} ({dt:.1f}s)"
            )
            n_done += 1
        db.save()
        print(
            f"pretune: {n_done} launch contexts tuned, {len(db)} records in "
            f"{args.db} ({time.perf_counter() - t_all:.1f}s); "
            f"{totals['measured']} candidates scored ({mode}), "
            f"{totals['pruned']} constraint-pruned at zero cost"
        )
        if n_failed:
            print(f"pretune: {n_failed} launch case(s) committed no record", file=sys.stderr)
            return 1
        return 0
    finally:
        sweep_span.__exit__(None, None, None)
        obs.shutdown()


def main(argv=None, prog: str = "repro.tuning.pretune") -> int:
    ap = argparse.ArgumentParser(
        prog=prog, description="offline tuning sweep -> JSON DB"
    )
    ap.add_argument("--db", type=str, default="tuned/cpu.json", help="DB file to fill")
    ap.add_argument("--smoke", action="store_true", help="tiny grid + budget (CI lane)")
    ap.add_argument(
        "--kernel", action="append", default=None, help="restrict to kernel(s); repeatable"
    )
    ap.add_argument(
        "--only", action="append", default=None, metavar="GLOB",
        help="restrict to matching cases, e.g. 'matmul/128*'; repeatable",
    )
    ap.add_argument(
        "--list", action="store_true", dest="list_grid",
        help="print the registered grid with DB hit status; tune nothing",
    )
    ap.add_argument("--num-opt", type=int, default=3, help="CSA coupled solvers")
    ap.add_argument("--max-iter", type=int, default=None, help="CSA iterations (default 2 smoke / 4)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--jobs", type=int, default=None,
        help="concurrent AOT compiles per tuning round (default: REPRO_TUNE_JOBS or cpu count)",
    )
    ap.add_argument(
        "--measure", choices=("adaptive", "fixed"), default=None,
        help="measurement policy: adaptive racing + roofline prefilter, or the "
             "classic fixed-repeat loop (default: REPRO_TUNE_MEASURE or adaptive)",
    )
    ap.add_argument(
        "--strategy", type=str, default=None, metavar="SPEC",
        help="search strategy spec per context, e.g. 'csa+nm' (the paper's "
             "CSA→NM hybrid pipeline), 'csa:0.7+nm:0.3', or 'csa|nm' "
             "(portfolio); default: plain CSA — same total tell budget either way",
    )
    ap.add_argument(
        "--objective", choices=("median", "p95", "p99"), default=None,
        help="statistic a candidate's measured repetitions reduce to "
             "(default median — classic PATSMA; p95/p99 tune for tail "
             "latency and stamp the objective on the committed records)",
    )
    ap.add_argument(
        "--shard", type=str, default=None, metavar="I/N",
        help="tune only this worker's deterministic slice of the grid "
             "(stable context-fingerprint hash mod N — N workers with "
             "--shard 0/N .. (N-1)/N cover the grid exactly once with zero "
             "coordination; merge the per-shard DBs with "
             "'python -m repro.tune db merge')",
    )
    ap.add_argument(
        "--cost", choices=("runtime", "analytic"), default="runtime",
        help="candidate cost: measured wall-clock (default) or the "
             "deterministic roofline lower bound of the compiled candidate — "
             "host-independent and noise-free, so sharded and unsharded "
             "sweeps land on identical best points (the CI equivalence lane)",
    )
    ap.add_argument(
        "--no-warm-start", action="store_true",
        help="disable DB neighbor seeding: each context's search is "
             "independent of sweep order and of what the DB already holds "
             "(required for exact shard-equivalence)",
    )
    ap.add_argument(
        "--obs-dir", type=str, default=None, metavar="DIR",
        help="write observability artifacts (events.jsonl, trace.json, "
             "metrics.json) into DIR; 'python -m repro.tune report DIR' "
             "renders them (default: the REPRO_OBS env var, else off)",
    )
    ap.add_argument(
        "--resume", action="store_true",
        help="resume a killed sweep from its run journal (<db>.journal): "
             "cases already committed or failed are skipped, only "
             "interrupted and never-started cases are (re-)measured",
    )
    ap.add_argument(
        "--launch", action="store_true",
        help="tune the launch-level grid (launch.spaces: mesh dp×tp "
             "factorization, microbatches, remat, collective chunking, XLA "
             "preset) instead of kernel tiles.  '--cost analytic' (the CI "
             "mode) scores candidates with the deterministic launch cost "
             "model; '--cost runtime' compiles each candidate on the "
             "host-platform mesh via launch.dryrun and charges its roofline "
             "bound",
    )
    ap.add_argument(
        "--devices", type=int, default=None,
        help="device count the launch grid factorizes (with --launch; "
             "default: REPRO_DRYRUN_DEVICES, else 8)",
    )
    args = ap.parse_args(argv)

    from repro import obs
    from repro.kernels.autotuned import exec_cache, registered, tune_call
    from repro.kernels.ops import interpret_mode
    from repro.tuning import TuningDB, default_device

    if args.obs_dir:
        obs.configure(args.obs_dir)
    else:
        obs.configure_from_env()

    max_iter = args.max_iter if args.max_iter is not None else (2 if args.smoke else 4)
    db = TuningDB(args.db)
    backend, device_kind = default_device()
    interpret = interpret_mode()
    print(f"pretune: db={args.db} ({len(db)} records) device={backend}/{device_kind}"
          f"{' (interpret mode)' if interpret else ''}")

    if args.launch:
        return _launch_main(args, db, max_iter=max_iter)

    wanted = set(args.kernel) if args.kernel else None
    unknown = (wanted or set()) - set(registered())
    if unknown:
        print(f"pretune: unknown kernel(s) {sorted(unknown)}", file=sys.stderr)
        return 2

    cases = _selected(
        _cases(args.smoke, abstract=args.list_grid), wanted, args.only
    )
    if not cases:
        print("pretune: no cases match the given filters", file=sys.stderr)
        return 2
    if args.shard is not None:
        from repro.tuning.fleet import parse_shard

        index, num = parse_shard(args.shard)
        total = len(cases)
        cases = _shard_filter(cases, args.smoke, wanted, args.only,
                              (index, num), interpret=interpret)
        print(f"pretune: shard {index}/{num}: {len(cases)}/{total} cases")
        if not cases:
            # an empty shard is a fleet worker with nothing to do, not an error
            db.save()
            return 0
    if args.list_grid:
        return _list_grid(cases, db, interpret=interpret)

    cost_fn = _analytic_cost_fn() if args.cost == "analytic" else None

    # write-ahead run journal: 'start' before each case's measurement,
    # 'commit'/'failed' after — a killed shard restarts with --resume
    # re-measuring nothing already completed
    import os

    from repro.tuning import RunJournal

    jpath = RunJournal.path_for(args.db)
    done_keys: set = set()
    if args.resume:
        journal = RunJournal(jpath)
        s = journal.summary()
        done_keys = set(s["committed"]) | set(s["failed"])
        if s["committed"]:
            # belt-and-braces: the journal carries full committed records, so
            # even a DB save torn by the kill is reconstructed here
            db.merge(journal.to_db())
        journal.resume()
        print(
            f"pretune: resume from {jpath}: {len(s['committed'])} committed, "
            f"{len(s['failed'])} failed, {len(s['interrupted'])} interrupted; "
            f"skipping {len(done_keys)} completed cases"
        )
    else:
        if os.path.exists(jpath):
            os.remove(jpath)  # a fresh sweep owns a fresh journal
        journal = RunJournal(jpath)

    n_done = 0
    n_skipped = 0
    n_failed = 0
    t_all = time.perf_counter()
    # aggregate measurement-engine counters across the sweep (run summary)
    totals = {"reps": 0, "warmup_reps": 0, "calibration_reps": 0,
              "culled": 0, "pruned_roofline": 0, "measured": 0, "failed": 0}
    # root span: every search/round/compile span of the sweep nests here,
    # and shutdown() flushes trace.json + metrics.json even on a crash
    sweep_span = obs.span("pretune", cases=len(cases))
    sweep_span.__enter__()
    try:
        for name, label, build in cases:
            call_args = build()
            key = _case_key(name, call_args, interpret=interpret)
            if key.encode() in done_keys:
                n_skipped += 1
                continue
            t0 = time.perf_counter()
            mstats: dict = {}
            journal.start(key)
            rec = tune_call(
                name,
                *call_args,
                db=db,
                interpret=interpret,
                num_opt=args.num_opt,
                max_iter=max_iter,
                seed=args.seed,
                jobs=args.jobs,
                source="pretune",
                measure=args.measure,
                measure_stats=mstats,
                strategy=args.strategy,
                objective=args.objective,
                cost_fn=cost_fn,
                warm_start=not args.no_warm_start,
            )
            dt = time.perf_counter() - t0
            for k in totals:
                totals[k] += int(mstats.get(k, 0))
            if rec is None:
                journal.failed(key, "every candidate failed")
                print(f"  {name}/{label}: every candidate failed; nothing stored ({dt:.1f}s)",
                      file=sys.stderr)
                n_failed += 1
                continue
            journal.commit(key, rec)
            crashed = f" crashed={rec.crashed}" if rec.crashed else ""
            strat = f" strategy={rec.strategy}" if rec.strategy and rec.strategy != "csa" else ""
            obj = f" objective={rec.objective}" if rec.objective and rec.objective != "median" else ""
            raced = ""
            if mstats.get("mode") == "adaptive" and mstats.get("measured"):
                raced = (f" reps={mstats['reps']}"
                         f" culled={mstats['culled']}"
                         f" pruned={mstats['pruned_roofline']}")
            print(
                f"  {name}/{label}: best={rec.point} cost={rec.cost * 1e3:.2f}ms "
                f"evals={rec.evals}{crashed}{strat}{obj}{raced} ({dt:.1f}s)"
            )
            n_done += 1
        db.save()
        cs = exec_cache().stats()
        skipped = f", {n_skipped} resumed-as-done" if n_skipped else ""
        print(
            f"pretune: {n_done} contexts tuned{skipped}, {len(db)} records in {args.db} "
            f"({time.perf_counter() - t_all:.1f}s); exec cache: {cs['misses']} compiles, "
            f"{cs['hits']} hits, {cs['recompiles']} recompiles"
        )
        if totals["measured"] or totals["reps"]:
            print(
                f"pretune: measurement: {totals['reps']} reps "
                f"(+{totals['warmup_reps']} warmup, {totals['calibration_reps']} "
                f"calibration) over {totals['measured']} candidates; "
                f"{totals['culled']} culled by racing, "
                f"{totals['pruned_roofline']} roofline-pruned, "
                f"{totals['failed']} failed"
            )
        if n_failed:
            print(f"pretune: {n_failed} case(s) committed no record", file=sys.stderr)
            return 1
        return 0
    finally:
        sweep_span.__exit__(None, None, None)
        obs.shutdown()


if __name__ == "__main__":
    # thin shim: ``python -m repro.tuning.pretune`` is the historical entry
    # point; it now routes through the umbrella CLI (``python -m repro.tune
    # pretune``) so both spellings share one dispatch path
    import sys as _sys

    from repro.compile_cache import use_compile_cache
    from repro.tune import main as _tune_main

    use_compile_cache()

    raise SystemExit(_tune_main(["pretune", *_sys.argv[1:]]))
