"""Serving launcher: batched prefill + decode with a KV cache and
PATSMA-tuned decode fusion depth.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2_7b --tiny \
        --batch 8 --prompt-len 32 --gen 64 --db tuned/serve.json
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2_7b --layers 6 \
        --batch 8 --prompt-len 128 --gen 32     # published widths, 6 of 28 layers

Decode is routed through a :class:`repro.runtime.ContextRouter`: each
(arch × batch size) is its own tuning context keyed by ``TuningKey``
fingerprint, an ε-fraction of decode chunks explores a candidate fusion
depth ``k``, and the rest exploit the best known.  Candidate variants are
AOT-compiled on a background pool (and every candidate is prewarmed before
the first token), so the token stream never stalls on XLA.  A
``DriftDetector`` watches the per-token exploit costs and re-tunes the
context mid-stream — at half budget, seeded at the deployed ``k`` — when
they degrade.

With ``--db`` the tuned fusion depth persists across launches: the second
process with the same (arch, batch) context skips tuning entirely and
decodes at the stored-best ``k`` from the first token.  ``--no-tune --db``
replays that stored best statically (no exploration, no drift handling);
``--no-tune`` without a DB record falls back to ``k=1``.

``--db`` is repeatable: extra paths are fleet shard DBs (``repro.tune
pretune --shard i/n`` outputs) folded read-only into the first at startup
with the fleet's keep-better resolver — serving a host straight off its
fleet's shards without a separate ``repro.tune db merge`` step.  Only the
first path is written back to.
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro import configs, obs
from repro.core import ChoiceDim, SearchSpace
from repro.models import ExecConfig, Model
from repro.runtime import ContextRouter
from repro.tuning import TuningDB, make_key

#: candidate decode fusion depths (tokens emitted per dispatched scan)
DECODE_KS = (1, 2, 4, 8)


def replay_decode_k(db, key, *, gen: int, default: int = 1) -> int:
    """Stored-best decode ``k`` for a context, for static (``--no-tune``)
    serving: an exact DB hit replays its point, otherwise ``default``.
    Clamped to the stream length."""
    k = default
    if db is not None and key is not None:
        rec = db.get(key)
        if rec is not None and "k" in rec.point:
            k = int(rec.point["k"])
    return max(1, min(k, gen))


def decode_chunk(model, k: int):
    """The jitted decode of ``k`` greedy tokens in one dispatched scan:
    ``(params, token, states, pos) -> (token, states, pos, tokens)``.  Its
    HLO module is ``jit_decode_k<k>``, so a profiler trace tells the chunk
    sizes apart.

    The program does not own its input ``states``, so it cannot write them
    in place.  A lone step reads them and writes a fresh buffer whole.  A
    chunk of k > 1 runs every step in place (``Model.decode_step(in_place=
    True)``), one cache slot a layer, on the copy XLA makes of the input at
    entry: the whole state is written once a chunk, not k times."""
    in_place = k > 1

    def run(params, token, states, pos):
        def body(carry, _):
            token, states, pos = carry
            lg, states = model.decode_step(params, token, states, pos, in_place=in_place)
            with jax.named_scope("sample"):
                nxt = jnp.argmax(lg, -1).astype(jnp.int32)[:, None]
            return (nxt, states, pos + 1), nxt
        (token, states, pos), toks = jax.lax.scan(
            body, (token, states, pos), None, length=k)
        return token, states, pos, toks
    run.__name__ = f"decode_k{k}"
    return jax.jit(run)


def run_chunk(fn, params, token, states, pos):
    """Run one decode chunk ``fn`` (a :func:`decode_chunk` program) and wait
    for its tokens: ``(token, states, pos, tokens)``.  The call until it
    returns is the ``serve.dispatch`` span, the wait for the tokens
    ``serve.wait``.  Counts the chunk's steps that wrote a fresh state
    buffer whole (a lone step) and those that ran in place (every step of a
    longer chunk) as ``serve.decode_steps.fresh`` and
    ``serve.decode_steps.in_place``."""
    with obs.span("serve.dispatch", cat="serve"):
        token, states, pos, toks = fn(params, token, states, pos)
    with obs.span("serve.wait", cat="serve"):
        jax.block_until_ready(toks)
    k = toks.shape[0]
    obs.counter("serve.decode_steps.in_place" if k > 1 else "serve.decode_steps.fresh").inc(k)
    return token, states, pos, toks


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default="qwen2_7b")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the published config to this many layers "
                         "(widths untouched) so it fits the device")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=64)
    ap.add_argument("--no-tune", action="store_true")
    ap.add_argument("--db", type=str, default=None, action="append",
                    help="tuning DB path; persists the tuned decode k across "
                         "runs.  Repeatable: extra paths are fleet shard DBs "
                         "merged (keep-better) into the first at startup")
    ap.add_argument("--epsilon", type=float, default=0.25,
                    help="explored fraction of decode chunks while tuning")
    ap.add_argument("--objective", choices=("median", "p95", "p99"),
                    default="median",
                    help="statistic the decode-k search minimizes (p95/p99 "
                         "tune for tail latency; the drift detector watches "
                         "the same quantile)")
    ap.add_argument("--obs-dir", type=str, default=None,
                    help="write observability artifacts (events.jsonl, "
                         "trace.json, metrics.json) into this directory "
                         "(default: the REPRO_OBS env var, else off)")
    return ap


def main():
    ap = parser()
    args = ap.parse_args()
    if args.tiny and args.layers is not None:
        ap.error("--layers cuts the published config; it does not apply with --tiny")

    from repro.compile_cache import use_compile_cache

    use_compile_cache()

    if args.obs_dir:
        obs.configure(args.obs_dir)
    else:
        obs.configure_from_env()
    try:
        with obs.span("serve", gen=args.gen):
            _serve(args)
    finally:
        obs.shutdown()


def _serve(args) -> dict:
    """Prefill then decode ``args.gen`` tokens.  Returns the served
    ``model``/``params``, the prompt ``batch`` and the decode ``router``
    (``None`` with ``--no-tune``) for callers that check the run."""
    if args.tiny:
        cfg = configs.get_tiny(args.arch)
    else:
        cfg = configs.get(args.arch, n_layers=args.layers)
        if args.layers is not None:
            print(f"depth cut: {cfg.n_layers} of {configs.get(args.arch).n_layers} "
                  "layers; widths as published")
    model = Model(cfg, ExecConfig(rec_chunk=4))
    params = model.init(jax.random.PRNGKey(0))
    B, P = args.batch, args.prompt_len
    max_len = P + args.gen
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, P), 0, cfg.vocab_size),
             "max_len": max_len}
    if cfg.is_encdec:
        batch["frames"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.ctx_tokens, cfg.d_model))
    if cfg.family == "vlm":
        batch["ctx_embeds"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.ctx_tokens, cfg.d_model))

    t0 = time.perf_counter()
    hidden, states = model.prefill(params, batch)
    logits = model.logits(params, hidden[:, None])[:, 0]
    token = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    jax.block_until_ready(token)
    print(f"prefill {B}x{P}: {(time.perf_counter()-t0)*1e3:.0f} ms")

    def make_multi(k):
        return decode_chunk(model, k)

    # only depths that fit the stream are candidates: a k > --gen chunk can
    # never run whole, so it could never be measured and the search would
    # stall on it (short streams get their own space hash, hence their own
    # tuning context — a k=8-capable record says nothing about a 4-token job)
    ks = tuple(k for k in DECODE_KS if k <= args.gen) or (1,)
    space = SearchSpace([ChoiceDim("k", ks)])
    db = None
    if args.db:
        db = TuningDB(args.db[0])
        if len(args.db) > 1:
            from repro.tuning.fleet import merge_dbs

            stats = merge_dbs(db, [TuningDB(p, autosave=False) for p in args.db[1:]])
            print(f"merged {len(args.db) - 1} shard DB(s) into {args.db[0]}: {stats}")
    extra = {"arch": args.arch, "tiny": args.tiny, "batch": args.batch}
    if args.layers is not None:
        extra["layers"] = args.layers
    key = make_key("serve/decode_k", space=space, extra=extra) if db else None
    pos = jnp.int32(P)
    tail_fns = {}  # final-chunk sizes (k > remaining): compiled on demand

    if args.no_tune:
        # static serving still honours the DB: replay the stored-best k
        k_static = replay_decode_k(db, key, gen=args.gen)
        if db is not None and k_static != 1:
            print(f"--no-tune: replaying stored decode k={k_static} from {args.db[0]}")
        fn_static = make_multi(k_static).lower(params, token, states, pos).compile()
        emitted = 0
        t0 = time.perf_counter()
        while emitted < args.gen:
            k = min(k_static, args.gen - emitted)
            fn = fn_static if k == k_static else tail_fns.setdefault(k, make_multi(k))
            token, states, pos, toks = run_chunk(fn, params, token, states, pos)
            emitted += k
        wall = time.perf_counter() - t0
        print(f"decode: {emitted} tok/seq x {B} in {wall*1e3:.0f} ms "
              f"({B*emitted/wall:.0f} tok/s); static k={k_static}")
        return {"model": model, "params": params, "batch": batch, "router": None}

    # adaptive serving: per-(arch, batch) decode-k context with background
    # candidate compiles and mid-stream drift re-tuning
    router = ContextRouter(db=db, jobs=max(1, len(DECODE_KS)))
    router.register(
        "serve/decode_k",
        space=lambda: space,
        build=lambda point: make_multi(point["k"]).lower(
            params, token, states, pos).compile(),
        defaults=lambda: {"k": 1},
        epsilon=args.epsilon,
        num_opt=3,
        max_iter=4,
        # tail objectives need a multi-rep stream per candidate (the p99 of
        # one rep is that rep); the median default keeps the classic
        # one-explore-one-measurement serving loop
        measure=None if args.objective == "median"
        else {"mode": "fixed", "repeats": 8, "objective": args.objective},
        drift={"window": 8, "min_samples": 4, "factor": 1.5},
        extra=extra,
    )
    tuner = router.tuner("serve/decode_k")
    # prewarm every candidate that fits the stream (on a DB hit, just the
    # stored best) so the first token needs zero in-band compiles
    if tuner.finished:
        points = [{"k": min(int(tuner.best_point["k"]), args.gen)}]
        print(f"tuning db hit: decode k={tuner.best_point['k']} (no online tuning)")
    else:
        points = [{"k": k} for k in ks]
    t0 = time.perf_counter()
    tuner.prewarm(points, wait=True)
    print(f"precompiled decode variants k={[p['k'] for p in points]} "
          f"in {(time.perf_counter() - t0) * 1e3:.0f} ms")

    emitted = 0
    t0 = time.perf_counter()
    while emitted < args.gen:
        rem = args.gen - emitted
        if rem < ks[-1]:
            # stream tail: not every candidate fits any more, so don't
            # consume a routing decision that might be unmeasurable — serve
            # the clamped best unmeasured (a shorter scan is a different
            # program, its cost would not describe the candidate's k)
            k = max(1, min(int(tuner.exploit_point().get("k", 1)), rem))
            fn = tuner.executable_for({"k": k}) if k in ks else None
            if fn is None:
                fn = tail_fns.setdefault(k, make_multi(k))
            token, states, pos, toks = run_chunk(fn, params, token, states, pos)
            emitted += k
            continue
        decision = router.begin("serve/decode_k")
        k = int(decision.point["k"])  # always <= ks[-1] <= rem here
        tc = time.perf_counter()
        fn = decision.executable
        if fn is None:  # cold exploit before the background build lands
            fn = tail_fns.setdefault(k, make_multi(k))
        token, states, pos, toks = run_chunk(fn, params, token, states, pos)
        router.observe(decision, (time.perf_counter() - tc) / k)
        emitted += k
    wall = time.perf_counter() - t0
    rs = router.stats()
    print(f"decode: {emitted} tok/seq x {B} in {wall*1e3:.0f} ms "
          f"({B*emitted/wall:.0f} tok/s); tuned k={tuner.best_point.get('k')}")
    print(f"router: {rs['explores']} explore / {rs['exploits']} exploit chunks, "
          f"{rs['drift_resets']} drift re-tunes, "
          f"{rs['cache']['misses']} compiles ({rs['inband_builds']} in-band), "
          f"{rs['cache']['recompiles']} recompiles")
    return {"model": model, "params": params, "batch": batch, "router": router}


if __name__ == "__main__":
    main()
