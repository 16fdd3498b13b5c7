"""DB-backed entry point for the Pallas kernels.

``autotuned(name, *args)`` is the one call sites use when they want tuned
block shapes without owning a tuning loop: it fingerprints the call context
(kernel name, input shapes/dtypes, search space, backend/device), consults the
:class:`repro.tuning.TuningDB`, and dispatches the kernel with

* the stored best on an **exact** fingerprint hit (zero overhead),
* a stored **neighbor**'s point clamped into this shape's space (near miss),
* the kernel's registered defaults on a cold miss — or, with ``tune=True``,
  a measured PATSMA search (warm-seeded from the neighbor when one exists)
  whose result is committed back to the DB.

The ``pretune`` CLI sweeps the registered grid below offline so production
processes and CI land on the first branch.

:func:`routed` is the *adaptive* dispatch on top: calls go through the
process-wide ``repro.runtime.ContextRouter`` (:func:`kernel_router`), which
keeps an ε-fraction of live traffic exploring candidates compiled off-thread
and re-tunes a context in the background when its costs drift.
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import Callable, Optional

import numpy as np

from repro.core import (
    Autotuning,
    ExecutableCache,
    FaultPolicy,
    GuardTimeout,
    LogIntDim,
    MeasureEngine,
    MeasurePolicy,
    MeasureResult,
    Quarantine,
    RuntimeCost,
    SearchSpace,
    compile_fanout,
    guarded_call,
    is_transient_failure,
    resolve_measure_policy,
    sandboxed_probe,
    time_rep,
)
from repro.core.guard import TRANSIENT_MARKERS as _TRANSIENT_MARKERS
from repro.core.measure import ENV_TUNE_MEASURE  # noqa: F401 - public re-export
from repro.obs import events as _events
from repro.obs.log import get_logger
from repro.obs.trace import tracer as _tracer
from repro.tuning import TuningDB, default_db, default_device, make_key

from . import ops

log = get_logger(__name__)

__all__ = [
    "autotuned",
    "routed",
    "kernel_router",
    "tune_call",
    "register",
    "get_spec",
    "registered",
    "KernelSpec",
    "exec_cache",
    "classify_failure",
]

#: env var: default compile fan-out width for tune_call (0/unset → cpu count)
ENV_TUNE_JOBS = "REPRO_TUNE_JOBS"

#: env var: default for tune_call's ``drain`` (finish all compiles before the
#: first measurement of a round instead of overlapping them)
ENV_TUNE_DRAIN = "REPRO_TUNE_DRAIN"


# ------------------------------------------------------------------ registry
@dataclasses.dataclass(frozen=True)
class KernelSpec:
    name: str
    fn: Callable  # fn(*args, **kwargs, **knobs, interpret=...)
    space: Callable  # space(*args, **kwargs) -> SearchSpace over the knobs
    defaults: Callable  # defaults(*args, **kwargs) -> dict of knob values


_REGISTRY: dict = {}


def register(spec: KernelSpec) -> KernelSpec:
    _REGISTRY[spec.name] = spec
    return spec


def get_spec(name: str) -> KernelSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown kernel {name!r}; registered: {sorted(_REGISTRY)}") from None


def registered() -> list:
    return sorted(_REGISTRY)


def _pow2_floor(n: int) -> int:
    """Largest power of two that divides n (n>0)."""
    return n & (-n)


def _log_dim(name: str, n: int, lo: int, cap: int) -> LogIntDim:
    """Power-of-two tile dim that always divides ``n``: bounds clamp to the
    largest power-of-two divisor of n, so every decodable value is legal."""
    g = _pow2_floor(int(n))
    lo = max(1, min(lo, g))
    hi = max(lo, min(cap, g, int(n)))
    return LogIntDim(name, lo, hi)


def _lane_tile(n: int, cap: int) -> int:
    """Largest tile the chip accepts along a block's last dimension: a
    multiple of 128 that divides ``n`` (a power of two up to ``cap``), or
    ``n`` itself when 128 does not divide it."""
    return n if n % 128 else min(cap, _pow2_floor(n))


def _lane_dim(name: str, n: int, cap: int) -> LogIntDim:
    """Tile dim along a block's last dimension, holding only tiles the TPU's
    (8, 128) block tiling accepts: 128 up to :func:`_lane_tile`, or just ``n``."""
    n = int(n)
    hi = _lane_tile(n, cap)
    return LogIntDim(name, hi if n % 128 else 128, hi)


# --------------------------------------------------------- registered kernels
register(
    KernelSpec(
        name="matmul",
        fn=ops.matmul,
        space=lambda a, b: SearchSpace(
            [
                _log_dim("bm", a.shape[0], 32, 256),
                # bn, bk are the last dims of the (bk, bn) / (bm, bk) blocks
                _lane_dim("bn", b.shape[1], 256),
                _lane_dim("bk", a.shape[1], 256),
            ]
        ),
        defaults=lambda a, b: {
            "bm": min(256, _pow2_floor(a.shape[0])),
            "bn": _lane_tile(b.shape[1], 256),
            "bk": _lane_tile(a.shape[1], 256),
        },
    )
)

register(
    KernelSpec(
        name="flash_attention",
        fn=ops.flash_attention,
        # q: (B,Sq,H,hd); k/v: (B,Kh,Skv,hd)
        space=lambda q, k, v, **kw: SearchSpace(
            [
                _log_dim("block_q", q.shape[1], 16, 1024),
                _log_dim("block_kv", k.shape[2], 16, 1024),
            ]
        ),
        defaults=lambda q, k, v, **kw: {
            "block_q": min(1024, _pow2_floor(q.shape[1])),
            "block_kv": min(1024, _pow2_floor(k.shape[2])),
        },
    )
)

register(
    KernelSpec(
        name="decode_attention",
        fn=ops.decode_attention,
        # q: (B,H,hd); k/v: (B,Kh,S,hd); valid: (B,S)
        # block_kv is the last dim of the mask's (1, 1, block_kv) block
        space=lambda q, k, v, valid: SearchSpace([_lane_dim("block_kv", k.shape[2], 1024)]),
        defaults=lambda q, k, v, valid: {"block_kv": _lane_tile(k.shape[2], 512)},
    )
)

register(
    KernelSpec(
        name="lru_scan",
        fn=ops.lru_scan,
        # a,b: (B,T,D); h0: (B,D)
        space=lambda a, b, h0: SearchSpace([_log_dim("chunk", a.shape[1], 16, 256)]),
        defaults=lambda a, b, h0: {"chunk": min(128, _pow2_floor(a.shape[1]))},
    )
)

register(
    KernelSpec(
        name="rwkv_scan",
        fn=ops.rwkv_scan,
        # r,k,v,lw: (B,T,H,hd); u: (H,hd); s0: (B,H,hd,hd)
        space=lambda r, k, v, lw, u, s0: SearchSpace(
            [_log_dim("chunk", r.shape[1], 16, 128)]
        ),
        defaults=lambda r, k, v, lw, u, s0: {"chunk": min(64, _pow2_floor(r.shape[1]))},
    )
)


# ------------------------------------------------------------------- tuning
#: substrings that mark an *expected* failure: a candidate whose tile/block
#: configuration is illegal for this shape or doesn't fit the target memory.
#: Anything else is an unexpected error — a real bug the search must not eat.
_ILLEGAL_MARKERS = (
    "block",
    "tile",
    "grid",
    "divisible",
    "divides",
    "not a multiple",
    "memory space",
    "vmem",
    "smem",
    "out of memory",
    "resource_exhausted",
    "resource exhausted",
    "mosaic",
)

#: exception types that are programmer errors no matter what the message says:
#: the knob names themselves ("block_q", "tile"...) show up in e.g. a TypeError
#: about an unknown kwarg, which must never pass for an illegal-tile failure
_BUG_EXC_TYPES = (TypeError, AttributeError, NameError, ImportError, SyntaxError)

# the transient-failure markers (RESOURCE_EXHAUSTED and friends) are shared
# with the guard layer — imported above as _TRANSIENT_MARKERS so both layers
# agree on what "worth retrying" means


def exec_cache() -> ExecutableCache:
    """The process-wide executable cache used by :func:`tune_call`."""
    return _EXEC_CACHE


def classify_failure(exc: BaseException) -> str:
    """``"illegal"`` (expected: bad tile for this shape/memory) or
    ``"unexpected"`` (a real bug that deserves a log line)."""
    if isinstance(exc, GuardTimeout):
        # a watchdog-expired candidate is an expected hazard of tuning on
        # live hardware, not a framework bug: charge inf quietly
        return "illegal"
    if isinstance(exc, _BUG_EXC_TYPES):
        return "unexpected"
    msg = f"{type(exc).__name__}: {exc}".lower()
    return "illegal" if any(m in msg for m in _ILLEGAL_MARKERS) else "unexpected"


def _failure_is_deterministic(exc: BaseException) -> bool:
    """Whether a build failure may be cached for the process lifetime.

    Only clearly deterministic illegal-tile failures qualify; unexpected
    errors, watchdog timeouts, and resource exhaustion (which can all be
    artifacts of concurrent compile load rather than the candidate itself)
    are retried on revisit."""
    return classify_failure(exc) == "illegal" and not is_transient_failure(exc)


#: process-level cache of AOT-compiled kernel executables, keyed by
#: (context fingerprint, decoded knobs) — revisited candidates across rounds,
#: optimizer resets, and pretune grid cells never recompile.  Only
#: deterministic illegal-tile failures are cached; transient/unexpected
#: build failures are retried on revisit.
_EXEC_CACHE = ExecutableCache(
    maxsize=int(os.environ.get("REPRO_EXEC_CACHE_SIZE", "1024")),
    cache_failures=_failure_is_deterministic,
)


def _resolve_jobs(jobs: Optional[int]) -> int:
    if jobs is None:
        jobs = int(os.environ.get(ENV_TUNE_JOBS, "0") or 0)
    if jobs <= 0:
        # leave one core for the serial measurement thread; on 1-2 core hosts
        # concurrent XLA compiles contend more than they overlap, so fall back
        # to the serial compile path there.  Capped at 8 by default — tuning
        # rounds rarely have more unique candidates, and wider fan-out mostly
        # adds compile memory pressure; pass jobs=/REPRO_TUNE_JOBS to exceed it
        jobs = min(8, max(1, (os.cpu_count() or 2) - 1))
    return max(1, jobs)


def _roofline_bound(ex) -> Optional[float]:
    """Analytic lower bound (seconds) of a compiled executable on the current
    device, or ``None`` when the device's kind has no published peaks
    (:func:`~repro.core.costs.peaks_for`) or cost analysis is unavailable.
    Conservative by construction: the bound assumes the device's peaks, so it
    can only *under*-estimate the real wall time — a candidate is pruned only
    when even its ideal execution loses to the incumbent's measured cost.
    Pass an explicit ``bound_fn`` to :func:`tune_call` for other bounds."""
    from repro.core import peaks_for, roofline_terms

    hw = peaks_for(default_device()[1])
    if hw is None:
        return None
    try:
        b = float(roofline_terms(ex, chips=1, hw=hw).bound_s)
    except Exception:
        return None
    return b if b > 0.0 else None


def tune_call(
    name: str,
    *args,
    db: Optional[TuningDB] = None,
    interpret: bool = False,
    num_opt: int = 3,
    max_iter: int = 4,
    seed: int = 0,
    warmup: int = 1,
    repeats: int = 2,
    verbose: bool = False,
    source: str = "online",
    jobs: Optional[int] = None,
    drain: Optional[bool] = None,
    cost_fn: Optional[Callable] = None,
    measure=None,
    bound_fn: Optional[Callable] = None,
    measure_stats: Optional[dict] = None,
    strategy: Optional[str] = None,
    objective: Optional[str] = None,
    warm_start: bool = True,
    fault_policy: Optional[FaultPolicy] = None,
    fault_plan=None,
    **kwargs,
):
    """Run a measured PATSMA search for this call context and commit the
    result to ``db``.  Warm-seeds from the nearest stored neighbor when one
    exists (half budget).  Returns the TuningRecord for the context.

    Candidates are evaluated in batches: each optimizer round is deduplicated,
    its unique points AOT-compiled concurrently (``jobs`` threads, default
    ``REPRO_TUNE_JOBS`` or min(8, CPU count − 1) — XLA compilation releases
    the GIL) through the process-level executable cache, and then measured
    strictly serially (one candidate at a time) so wall-clock timings stay
    honest.

    ``measure`` picks the measurement policy (a
    :class:`~repro.core.measure.MeasurePolicy`, ``"adaptive"``, ``"fixed"``,
    or ``None`` → the ``REPRO_TUNE_MEASURE`` env var, default adaptive):

    * **adaptive** — the racing engine: every candidate of a round gets one
      measured repetition, dominated candidates are culled at their single-rep
      cost, survivors escalate through the repeat ladder until separated;
      candidates whose roofline lower bound already exceeds the incumbent's
      measured cost skip measurement entirely.  The whole round's compiles
      are drained before the first rep (racing compares candidates within a
      round, so timings must not run against background compile load).
    * **fixed** — the classic :class:`RuntimeCost` ``warmup``/``repeats``
      median per candidate, trajectory-identical to earlier releases; early
      candidates' measurements overlap the remaining compiles unless
      ``drain=True`` (or ``REPRO_TUNE_DRAIN=1``).

    Failures are classified either way: expected illegal-tile candidates
    quietly cost ``inf``, each distinct unexpected error is logged once per
    search, and the committed record carries a ``crashed`` count plus the
    best point's ``cost_std``/``repeats_spent`` measurement confidence.

    ``cost_fn(executable, *args) -> float`` overrides wall-clock timing
    (tests and ``pretune --cost analytic`` use it for deterministic costs);
    under the adaptive policy each call supplies one repetition, and the
    roofline prefilter is disabled unless an explicit ``bound_fn(point,
    executable)`` provides bounds in the cost function's own units.
    ``measure_stats``, if given a dict, receives the measurement engine's
    counters (reps spent, culls, roofline prunes) when the search finishes.

    ``strategy`` picks the search strategy (``"csa+nm"``, ``"csa|nm"``, ...
    — the :func:`repro.core.strategy.make_strategy` grammar) over the same
    ``num_opt * max_iter`` tell budget the default CSA consumes; ``None``
    keeps the classic CSA search, trajectory-identical to earlier releases.
    A :class:`~repro.core.strategy.Portfolio` strategy reuses the adaptive
    engine's calibrated noise floor for its statistically-separated-lead
    culls.  The spec is stamped on the committed record (``strategy``).

    ``objective`` picks the statistic a candidate's repetitions reduce to
    (``"median"`` default, ``"p95"``, ``"p99"`` — see
    :data:`repro.core.measure.OBJECTIVES`).  Tail objectives tune for
    worst-case latency: the search minimizes the chosen quantile of each
    candidate's measured repetitions, and the committed record is stamped
    with the objective so a p99 cost is never compared against a median one.

    ``warm_start=False`` disables the DB neighbor seeding, making each
    context's search independent of what else the DB holds — the fleet's
    shard-equivalence contract (a sharded sweep must reproduce the
    unsharded sweep's points) needs searches whose trajectories do not
    depend on the sweep's visiting order.

    ``fault_policy`` (a :class:`~repro.core.guard.FaultPolicy`, default
    ``None`` = unguarded, trajectory-identical to earlier releases) arms the
    resilience layer: per-stage watchdog timeouts charge hung candidates
    ``inf`` instead of wedging the run, transient failures
    (RESOURCE_EXHAUSTED class) are retried in place with deterministic
    backoff, a candidate failing ``max_failures`` times is quarantined
    (skipped without a build, charged ``inf``), and with
    ``sandbox_first_touch`` each never-seen candidate is crash-probed in a
    forked child first so a hard crash is contained.  ``fault_plan`` injects
    a deterministic :class:`~repro.testing.faults.FaultPlan` at the
    ``"tune"``/``"build"``/``"cost"`` seams (``None`` reads the
    ``REPRO_FAULT_PLAN`` env var — the chaos CI lane's hook).
    """
    import jax

    spec = get_spec(name)
    space = spec.space(*args, **kwargs)
    key = make_key(name, args=args, kwargs=kwargs, space=space,
                   extra={"interpret": bool(interpret)})
    db = db if db is not None else default_db()
    policy = resolve_measure_policy(
        measure, warmup=warmup, repeats=repeats, objective=objective
    )
    cost = cost_fn if cost_fn is not None else RuntimeCost(
        warmup=warmup, repeats=repeats, objective=policy.objective
    )
    jobs = _resolve_jobs(jobs)
    if drain is None:
        drain = bool(int(os.environ.get(ENV_TUNE_DRAIN, "0") or 0))
    ctx = key.encode()
    logged: set = set()  # distinct unexpected errors already reported

    # --- resilience layer (all opt-in; None → identical trajectories)
    if fault_plan is None:
        from repro.testing.faults import active_plan

        fault_plan = active_plan()
    plan = fault_plan
    fpol = fault_policy
    quarantine = Quarantine(fpol.max_failures) if fpol is not None else None

    def qkey(p: dict):
        return tuple(sorted(p.items()))

    if plan is not None:
        plan.fire("tune", key=name)

    fatal = None
    if fpol is not None and fpol.fail_fast:
        def fatal(e: BaseException) -> bool:
            # a poisoned round: an error that is neither an expected illegal
            # tile nor a load transient would hit every candidate identically
            return classify_failure(e) == "unexpected" and not is_transient_failure(e)
    compile_deadline = fpol.compile_deadline if fpol is not None else None

    def build_for(knobs: dict):
        def build():
            if plan is not None:
                plan.fire("build", key=knobs)
            fn = jax.jit(
                lambda *xs: spec.fn(*xs, **kwargs, **knobs, interpret=interpret)
            )
            return fn.lower(*args).compile()

        if fpol is None:
            return build

        def probed():
            if fpol.sandbox_first_touch:
                # crash canary: a hard crash dies in a forked child and
                # surfaces as SandboxCrash, charged inf by the layers above
                sandboxed_probe(
                    build, timeout=fpol.sandbox_timeout, label=f"{name}:{knobs}"
                )
            return build()

        return fpol.wrap(probed, stage="compile", label=f"{name}:build")

    def note_failure(knobs: dict, exc: BaseException, stage: str) -> None:
        if isinstance(exc, (KeyboardInterrupt, SystemExit)):
            raise exc  # user interrupt, not a candidate failure
        if quarantine is not None and quarantine.note_failure(qkey(knobs)):
            log.info(
                "%s: candidate %s quarantined after %d failures",
                name, knobs, quarantine.max_failures,
            )
        kind = classify_failure(exc)
        if kind == "unexpected":
            sig = (type(exc).__name__, str(exc).splitlines()[0] if str(exc) else "")
            if sig not in logged:
                logged.add(sig)
                log.warning(
                    "%s: unexpected %s error for %s: %s: %s",
                    name, stage, knobs, type(exc).__name__, exc,
                )
        elif verbose:
            log.info("%s: illegal candidate %s: %s", name, knobs, exc)

    # fixed-path counters (the adaptive engine keeps its own): measure_stats
    # must report repetitions spent in either mode
    fixed_counts = {"rounds": 0, "candidates": 0, "measured": 0, "failed": 0,
                    "reps": 0, "warmup_reps": 0, "timeouts": 0, "retried": 0}

    # obs forensics: every candidate a round asks for gets exactly one
    # terminal event — this is the completeness invariant the acceptance
    # gate checks (committed + culled + pruned + skipped + quarantined =
    # asked).  Emission lives here, after measurement, because only this
    # frame sees both the quarantine decision and the final MeasureResult.
    ev_round = [0]

    def emit_round_events(points, live, results) -> None:
        if _events.sink() is None:
            return
        ev_round[0] += 1
        sname = at.ctx_name()
        rnd = ev_round[0]
        live_set = set(live)
        for i, p in enumerate(points):
            _events.emit("candidate_asked", name=sname, point=dict(p), round=rnd)
            if i not in live_set:
                _events.emit("candidate_quarantined", name=sname, point=dict(p))
                continue
            r = results[i]
            if isinstance(r, MeasureResult):
                if r.pruned is not None:
                    _events.emit("candidate_pruned", name=sname, point=dict(p),
                                 bound=float(r.cost))
                elif r.culled:
                    _events.emit("candidate_culled", name=sname, point=dict(p),
                                 cost=float(r.cost), ci_lo=float(r.ci_lo),
                                 ci_hi=float(r.ci_hi))
                elif math.isfinite(r.cost):
                    _events.emit("candidate_committed", name=sname,
                                 point=dict(p), cost=float(r.cost))
                else:
                    _events.emit("candidate_skipped", name=sname,
                                 point=dict(p), reason="failed")
            else:
                c = float(r)
                if math.isfinite(c):
                    _events.emit("candidate_committed", name=sname,
                                 point=dict(p), cost=c)
                else:
                    _events.emit("candidate_skipped", name=sname,
                                 point=dict(p), reason="failed")

    def measure_one(p, ex):
        if isinstance(ex, BaseException):
            note_failure(p, ex, "compile")
            fixed_counts["failed"] += 1
            return np.inf

        def run():
            if plan is not None:
                plan.fire("cost", key=p)
            return float(cost(ex, *args))

        try:
            if fpol is not None and (
                fpol.measure_timeout is not None or fpol.retries > 0
            ):
                c = guarded_call(
                    run,
                    timeout=fpol.measure_timeout,
                    retries=fpol.retries,
                    backoff=fpol.backoff,
                    backoff_mult=fpol.backoff_mult,
                    jitter=fpol.jitter,
                    label=f"{name}:measure",
                    on_retry=lambda *_: fixed_counts.__setitem__(
                        "retried", fixed_counts["retried"] + 1
                    ),
                )
            else:
                c = run()
        except Exception as e:
            if isinstance(e, GuardTimeout):
                fixed_counts["timeouts"] += 1
            note_failure(p, e, "measure")
            fixed_counts["failed"] += 1
            return np.inf
        if quarantine is not None:
            quarantine.note_success(qkey(p))
        fixed_counts["measured"] += 1
        if isinstance(cost, RuntimeCost):
            fixed_counts["reps"] += len(cost.last_times)
            fixed_counts["warmup_reps"] += cost.warmup
            # surface the fixed schedule's measurement confidence too
            return MeasureResult(
                cost=c,
                cost_std=cost.last_std,
                repeats_spent=len(cost.last_times),
                times=list(cost.last_times),
            )
        fixed_counts["reps"] += 1  # one cost_fn call per candidate
        return c

    def measure_batch_fixed(points):
        # Concurrent AOT compile of the round's unique candidates, deduped
        # against every executable this process ever built; wall-clock
        # measurement stays strictly serial (one candidate at a time, in
        # order) but overlaps the *remaining* compiles — candidate i is
        # measured as soon as its executable is ready while i+1.. still
        # compile on the pool (``drain`` trades that overlap for unbiased
        # timings).
        fixed_counts["rounds"] += 1
        fixed_counts["candidates"] += len(points)
        results: list = [None] * len(points)
        live: list = []  # indices not quarantined
        for i, p in enumerate(points):
            if quarantine is not None and qkey(p) in quarantine:
                results[i] = np.inf  # skipped outright: no build, no measure
            else:
                live.append(i)
        items = [((ctx, qkey(points[i])), build_for(points[i])) for i in live]
        if jobs <= 1 or len(items) <= 1 or compile_deadline is not None or fatal:
            # the serial path — and, when a round deadline or fail-fast is
            # armed, the managed fan-out (compile/measure overlap is traded
            # for cancellable builds)
            compiled = compile_fanout(
                items,
                cache=_EXEC_CACHE,
                jobs=1 if jobs <= 1 else min(jobs, max(1, len(items))),
                deadline=compile_deadline,
                fatal=fatal,
            )
            for i, ex in zip(live, compiled):
                results[i] = measure_one(points[i], ex)
            emit_round_events(points, live, results)
            return results
        from concurrent.futures import ThreadPoolExecutor, wait

        with ThreadPoolExecutor(max_workers=min(jobs, len(items))) as pool:
            # wrap the build thunk, not the lookup: cache hits cost no span
            tr = _tracer()
            futs = [pool.submit(_EXEC_CACHE.get_or_build, k,
                                tr.wrap(b, "compile"))
                    for k, b in items]
            if drain:  # no compile runs in the background of any measurement
                wait(futs)
            for i, f in zip(live, futs):
                results[i] = measure_one(points[i], f.result())
        emit_round_events(points, live, results)
        return results

    # --- adaptive policy: racing engine over each compiled round
    analytic = bound_fn if bound_fn is not None else (
        _roofline_bound_for if cost_fn is None else None
    )

    def make_rep(p, ex):
        def rep():
            if plan is not None:
                plan.fire("cost", key=p)
            t = float(cost_fn(ex, *args)) if cost_fn is not None else time_rep(ex, *args)
            if quarantine is not None:
                quarantine.note_success(qkey(p))
            return t

        return rep

    engine_policy = policy
    if cost_fn is not None and policy.mode == "adaptive" and not isinstance(
        measure, MeasurePolicy
    ):
        # a user cost function owns its own stabilization (per-rep warmup
        # would burn extra cost_fn calls) and returns costs in *its own
        # units* — the seconds-denominated abs_noise prior would swamp
        # small-magnitude costs and disable racing, so only the relative
        # floor applies.  An explicitly passed MeasurePolicy is authoritative.
        import dataclasses as _dc

        engine_policy = _dc.replace(policy, warmup=0, abs_noise=0.0)
    engine = MeasureEngine(engine_policy, guard=fpol)

    def measure_batch_adaptive(points):
        # racing compares candidates *within* the round, so the round's
        # compiles are always drained before the first repetition — overlap
        # would bias early candidates against late ones
        if engine.noise is not None and hasattr(at.optimizer, "set_noise"):
            # a Portfolio strategy separates leads with the same noise floor
            # the engine calibrated for candidate racing
            at.optimizer.set_noise(engine.noise)
        live = [
            i for i, p in enumerate(points)
            if quarantine is None or qkey(p) not in quarantine
        ]
        items = [((ctx, qkey(points[i])), build_for(points[i])) for i in live]
        compiled_live = compile_fanout(
            items,
            cache=_EXEC_CACHE,
            jobs=min(jobs, max(1, len(items))),
            deadline=compile_deadline,
            fatal=fatal,
        )
        by_index = dict(zip(live, compiled_live))
        # bounds are only worth computing once a finite incumbent exists —
        # the prefilter is disabled before the first measured round anyway,
        # so round 1 never pays HLO cost analysis per candidate
        want_bounds = analytic is not None and math.isfinite(engine.best_measured)
        reps, bounds = [], []
        for i, p in enumerate(points):
            ex = by_index.get(i)  # quarantined candidates never compiled
            if ex is None or isinstance(ex, BaseException):
                if ex is not None:
                    note_failure(p, ex, "compile")
                reps.append(None)
                bounds.append(None)
            else:
                reps.append(make_rep(p, ex))
                bounds.append(analytic(p, ex) if want_bounds else None)
        engine.on_error = lambda i, e: note_failure(points[i], e, "measure")
        results = engine.measure_round(reps, bounds=bounds)
        emit_round_events(points, live, results)
        return results

    measure_batch = (
        measure_batch_adaptive if policy.mode == "adaptive" else measure_batch_fixed
    )
    at = Autotuning(
        space=space,
        ignore=0,  # RuntimeCost already discards warmup runs
        search=strategy,  # None -> the classic default CSA search
        num_opt=num_opt,
        max_iter=max_iter,
        seed=seed,
        cache=True,
        verbose=verbose,
        db=db,
        key=key,
        warm_start=warm_start,
        db_source=source,
        objective=policy.objective,
    )
    at.entire_exec_batch(measure_batch)
    at.commit()  # no-op if auto-committed / exact hit
    if measure_stats is not None:
        if policy.mode == "fixed":
            stats = dict(engine.stats)  # zeroed template (right key set)
            stats.update(fixed_counts)
        else:
            stats = dict(engine.stats)
            if engine.noise is not None:
                stats["noise_abs_floor"] = engine.noise.abs_floor
                stats["noise_rel"] = engine.noise.rel
        stats["mode"] = policy.mode
        if quarantine is not None:
            stats["quarantined"] = quarantine.stats()["quarantined"]
        if plan is not None:
            stats["faults_fired"] = plan.count()
        measure_stats.update(stats)
    return db.get(key)


def _roofline_bound_for(point: dict, ex) -> Optional[float]:
    """Default ``bound_fn``: roofline lower bound of the compiled candidate
    (the point itself is already baked into the executable)."""
    return _roofline_bound(ex)


# --------------------------------------------------- router-backed dispatch
_ROUTERS: dict = {}  # interpret flag -> process-wide ContextRouter
_ROUTER_EPSILON = 0.1


def _router_build(spec: KernelSpec, interpret: bool) -> Callable:
    """AOT-compile one candidate; runs on the router's background pool."""

    def build(point: dict, *args, **kwargs):
        import jax

        fn = jax.jit(lambda *xs: spec.fn(*xs, **kwargs, **point, interpret=interpret))
        return fn.lower(*args).compile()

    return build


def kernel_router(
    *,
    interpret: bool = False,
    db: Optional[TuningDB] = None,
    epsilon: float = _ROUTER_EPSILON,
    jobs: Optional[int] = None,
    fresh: bool = False,
):
    """The process-wide :class:`repro.runtime.ContextRouter` over every
    registered kernel (one router per ``interpret`` flavour).

    Contexts are (kernel × pow2 shape-bucket); each starts from the tuning
    DB (exact pretuned fingerprints replay instantly, neighbors warm-start a
    half-budget search) and keeps adapting online: an ``epsilon`` fraction
    of live calls measures a candidate whose executable was AOT-compiled on
    the background pool through the shared process executable cache, and
    drift in the exploit costs triggers a warm re-search.  ``fresh=True``
    builds an independently configured router (tests, custom db/epsilon)
    instead of the cached singleton; asking the existing singleton for a
    different configuration is an error, not a silent no-op.
    """
    from repro.runtime.context import ContextRouter

    flag = bool(interpret)
    if not fresh and flag in _ROUTERS:
        if db is not None or epsilon != _ROUTER_EPSILON or jobs is not None:
            raise ValueError(
                f"kernel_router(interpret={flag}) is already configured; "
                "pass fresh=True for a differently-configured router"
            )
        return _ROUTERS[flag]
    router = ContextRouter(
        db=db if db is not None else default_db(),
        cache=_EXEC_CACHE,
        jobs=_resolve_jobs(jobs),
    )
    for name in registered():
        spec = get_spec(name)
        router.register(
            name,
            space=spec.space,
            defaults=spec.defaults,
            build=_router_build(spec, flag),
            epsilon=epsilon,
            extra={"interpret": flag},
        )
    if not fresh:
        _ROUTERS[flag] = router
    return router


def routed(
    name: str,
    *args,
    router=None,
    interpret: bool = False,
    **kwargs,
):
    """Adaptive kernel dispatch: like :func:`autotuned`, but every call flows
    through the kernel router — knobs keep improving while the process
    serves, and a drifted context re-tunes itself in the background.

    The serving call never compiles a *candidate* in-band: exploration only
    happens once the candidate's executable is ready in the process cache.
    The fallback path (no executable yet for the exploit knobs — e.g. the
    very first call of a cold context) dispatches the kernel directly.
    """
    import time as _time

    import jax

    r = router if router is not None else kernel_router(interpret=interpret)
    decision = r.begin(name, *args, **kwargs)
    t0 = _time.perf_counter()
    if decision.executable is not None:
        out = decision.executable(*args)
    else:
        # fallback dispatch: the router already clamped the knobs from the
        # shape-bucket's space into this exact shape's space
        spec = get_spec(name)
        out = spec.fn(*args, **kwargs, **decision.point, interpret=interpret)
    out = jax.block_until_ready(out)
    r.observe(decision, _time.perf_counter() - t0)
    return out


def autotuned(
    name: str,
    *args,
    db: Optional[TuningDB] = None,
    tune: bool = False,
    interpret: bool = False,
    **kwargs,
):
    """Dispatch kernel ``name`` with the best knobs known for this context."""
    spec = get_spec(name)
    space = spec.space(*args, **kwargs)
    key = make_key(name, args=args, kwargs=kwargs, space=space,
                   extra={"interpret": bool(interpret)})
    db = db if db is not None else default_db()
    rec, exact = db.lookup(key)
    if not exact and tune:
        tuned_rec = tune_call(name, *args, db=db, interpret=interpret, **kwargs)
        if tuned_rec is not None:  # all-crashed run: keep the neighbor fallback
            rec, exact = tuned_rec, True
    if exact:
        knobs = {n: rec.point[n] for n in space.names}
    elif rec is not None and all(n in rec.point for n in space.names):
        # neighbor: reuse its point, clamped into this shape's (smaller) space
        knobs = space.decode(space.encode(rec.point))
    else:
        knobs = spec.defaults(*args, **kwargs)
    return spec.fn(*args, **kwargs, **knobs, interpret=interpret)
