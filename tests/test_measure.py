"""Adaptive measurement engine: racing, noise floor, roofline prefilter,
record confidence, and the online fractional explore credits."""
from __future__ import annotations

import math

import numpy as np
import pytest

from repro.core import (
    CSA,
    Autotuning,
    IntDim,
    LogIntDim,
    MeasureEngine,
    MeasurePolicy,
    MeasureResult,
    RuntimeCost,
    SearchSpace,
    resolve_measure_policy,
)


def det_reps(costs, jitter=0.0):
    """Deterministic rep callables: candidate i returns costs[i] with an
    optional seeded pseudo-jitter per repetition."""
    state: dict = {}

    def rep_for(i):
        def rep():
            k = state.get(i, 0)
            state[i] = k + 1
            j = jitter * ((((i * 31 + k * 17) % 7) - 3) / 3.0)
            return costs[i] * (1.0 + j)

        return rep

    return [rep_for(i) for i in range(len(costs))]


# ---------------------------------------------------------------- the policy
def test_resolve_policy_from_env_and_values(monkeypatch):
    assert resolve_measure_policy("fixed").mode == "fixed"
    assert resolve_measure_policy("adaptive").mode == "adaptive"
    monkeypatch.setenv("REPRO_TUNE_MEASURE", "fixed")
    assert resolve_measure_policy(None).mode == "fixed"
    monkeypatch.delenv("REPRO_TUNE_MEASURE")
    assert resolve_measure_policy(None).mode == "adaptive"
    p = MeasurePolicy(mode="fixed", repeats=5)
    assert resolve_measure_policy(p) is p
    # warmup/repeats override named modes, never explicit policies
    assert resolve_measure_policy("fixed", warmup=0, repeats=9).repeats == 9
    with pytest.raises(ValueError):
        MeasurePolicy(mode="nope")
    with pytest.raises(ValueError):
        MeasurePolicy(ladder=(3, 1))


# ---------------------------------------------------------------- the engine
def test_racing_culls_dominated_candidate_after_one_rep():
    eng = MeasureEngine(MeasurePolicy(warmup=0, calibrate_reps=3))
    out = eng.measure_round(det_reps([1.0, 40.0, 5.0], jitter=1e-4))
    assert out[1].culled and out[1].repeats_spent == 1
    assert out[2].culled and out[2].repeats_spent == 1
    # culled candidates are charged their real single-rep cost, never inf
    assert out[1].cost == pytest.approx(40.0, rel=1e-3)
    assert np.isfinite(out[2].cost)
    # the winner survives un-culled
    assert not out[0].culled and out[0].cost == pytest.approx(1.0, rel=1e-3)
    assert eng.stats["culled"] == 2


def test_racing_never_culls_within_noise_floor():
    """Two candidates whose true costs sit inside the calibrated noise floor
    must both climb the full ladder — neither is raced out."""
    eng = MeasureEngine(MeasurePolicy(warmup=0, calibrate_reps=5))
    # 0.3% apart, jitter 0.5% -> calibrated floor covers the gap
    out = eng.measure_round(det_reps([1.0, 1.003, 30.0], jitter=5e-3))
    assert not out[0].culled and not out[1].culled
    assert out[0].repeats_spent == out[1].repeats_spent == 7  # ladder top
    assert out[2].culled and out[2].repeats_spent == 1
    assert eng.noise is not None and eng.noise.floor(1.0) >= 0.003


def test_racing_stops_early_when_separated():
    """Clearly distinct survivors do not climb past the first rung."""
    eng = MeasureEngine(MeasurePolicy(warmup=0, calibrate_reps=3))
    out = eng.measure_round(det_reps([1.0, 2.0], jitter=1e-4))
    # 2.0 is culled at rung 1; the singleton winner needs no more reps
    assert out[0].repeats_spent == 1
    assert out[1].culled


def test_racing_culls_regressive_round_against_incumbent():
    """A later round whose candidates all lose to an earlier round's best
    is decided at one rep each — mutual CI overlap must not escalate the
    ladder when the cross-round incumbent already dominates everyone."""
    eng = MeasureEngine(MeasurePolicy(warmup=0, calibrate_reps=3))
    eng.measure_round(det_reps([1.0], jitter=1e-4))
    out = eng.measure_round(det_reps([8.0, 8.001, 8.002], jitter=1e-4))
    assert all(r.culled and r.repeats_spent == 1 for r in out)
    assert eng.best_measured == pytest.approx(1.0, rel=1e-3)


def test_failed_and_missing_candidates_are_inf():
    eng = MeasureEngine(MeasurePolicy(warmup=0, calibrate_reps=2))

    def boom():
        raise ValueError("tile does not divide")

    errs = []
    eng.on_error = lambda i, e: errs.append((i, e))
    reps = det_reps([1.0, 1.0, 1.0])
    reps[1] = None  # executable never built
    reps[2] = boom
    out = eng.measure_round(reps)
    assert np.isfinite(out[0].cost)
    assert out[1].cost == math.inf and out[1].repeats_spent == 0
    assert out[2].cost == math.inf
    assert errs and errs[0][0] == 2
    assert eng.stats["failed"] == 2


def test_engine_reraises_interrupts():
    """A Ctrl-C mid-measurement is control flow, never a candidate cost."""
    eng = MeasureEngine(MeasurePolicy(warmup=0, calibrate_reps=2))

    def interrupted():
        raise KeyboardInterrupt

    with pytest.raises(KeyboardInterrupt):
        eng.measure_round(det_reps([1.0]) + [interrupted])


def test_roofline_prefilter_skips_and_charges_bound():
    eng = MeasureEngine(MeasurePolicy(warmup=0, calibrate_reps=2))
    eng.measure_round(det_reps([1.0]))  # establishes the incumbent
    out = eng.measure_round(det_reps([3.0, 0.5]), bounds=[2.7, 0.45])
    assert out[0].pruned == "roofline" and out[0].repeats_spent == 0
    assert out[0].cost == pytest.approx(2.7)
    assert out[1].pruned is None and np.isfinite(out[1].cost)
    # a pruned bound never becomes the incumbent
    assert eng.best_measured == pytest.approx(0.5, rel=1e-2)


def test_roofline_prefilter_never_fires_without_incumbent():
    eng = MeasureEngine(MeasurePolicy(warmup=0, calibrate_reps=2))
    out = eng.measure_round(det_reps([1.0, 2.0]), bounds=[0.9, 1.8])
    assert all(r.pruned is None for r in out)


def test_fixed_mode_spends_exact_schedule():
    eng = MeasureEngine(MeasurePolicy(mode="fixed", warmup=1, repeats=3))
    out = eng.measure_round(det_reps([1.0, 40.0], jitter=1e-4))
    assert [r.repeats_spent for r in out] == [3, 3]
    assert not any(r.culled for r in out)
    assert eng.stats["reps"] == 6 and eng.stats["warmup_reps"] == 2


# ------------------------------------------------- driver (entire_exec_batch)
def _bowl_space():
    return SearchSpace([LogIntDim("t", 4, 64)])


def _bowl_cost(point):
    return 1.0 + (math.log2(point["t"] / 16.0)) ** 2


def test_batch_driver_records_measure_meta_and_revisits_after_reset():
    """A roofline-pruned candidate is flagged in the driver's measurement
    meta; reset(level>=1) clears the flag and the re-search measures it."""
    space = _bowl_space()
    at = Autotuning(space=space, ignore=0,
                    optimizer=CSA(1, num_opt=4, max_iter=4, seed=0), cache=True)
    eng = MeasureEngine(MeasurePolicy(warmup=0, calibrate_reps=2))
    measured_points: list = []

    def measure_batch(points):
        measured_points.extend(tuple(sorted(p.items())) for p in points)
        reps = det_reps([_bowl_cost(p) for p in points])
        bounds = [0.9 * _bowl_cost(p) for p in points]
        return eng.measure_round(reps, bounds=bounds)

    at.entire_exec_batch(measure_batch)
    assert at.best_point == {"t": 16}
    pruned = [
        (p, at.measurement_meta(p)) for p, _ in at.history
        if (at.measurement_meta(p) or {}).get("pruned") == "roofline"
    ]
    assert eng.stats["pruned_roofline"] > 0 and pruned
    victim = pruned[0][0]
    # the meta survives a level-0 reset (history is kept)...
    # ...and a level-1 reset clears it so the point is re-measured
    at.reset(1)
    assert at.measurement_meta(victim) is None
    eng2 = MeasureEngine(MeasurePolicy(warmup=0, calibrate_reps=2))
    before = len(measured_points)

    def measure_batch2(points):
        measured_points.extend(tuple(sorted(p.items())) for p in points)
        return eng2.measure_round(det_reps([_bowl_cost(p) for p in points]))

    at.entire_exec_batch(measure_batch2)
    revisited = measured_points[before:]
    assert tuple(sorted(victim.items())) in revisited
    meta = at.measurement_meta(victim)
    assert meta is not None and meta["pruned"] is None
    assert meta["repeats_spent"] >= 1


def test_measure_meta_survives_pipeline_stage_transition():
    """A point really measured by an earlier pipeline stage must keep its
    measurement meta — and its measured cost — when a later stage revisits
    it and the engine's roofline prefilter answers with the optimistic
    analytic bound (NM must not 'improve' on CSA's real measurement)."""
    from repro.core import NelderMead, Pipeline

    space = SearchSpace([IntDim("k", 0, 31)])
    pipe = Pipeline(
        [CSA(1, num_opt=4, max_iter=3, seed=0),
         NelderMead(1, error=0.0, max_iter=100, seed=0)],
        (0.5, 0.5), budget=24,
    )
    # cache=False: revisits genuinely reach the measurement layer, which is
    # exactly when a stale prune could clobber a real measurement
    at = Autotuning(space=space, ignore=0, optimizer=pipe, cache=False)
    seen: set = set()

    def true_cost(p):
        return 1.0 + abs(p["k"] - 7) * 0.1

    def measure_batch(points):
        out = []
        for p in points:
            key = tuple(sorted(p.items()))
            if key in seen:
                # revisit: the engine prunes against its (better) incumbent,
                # charging an optimistic lower bound with zero reps
                out.append(MeasureResult(cost=0.5 * true_cost(p), pruned="roofline"))
            else:
                seen.add(key)
                out.append(
                    MeasureResult(cost=true_cost(p), cost_std=0.01, repeats_spent=3)
                )
        return out

    at.entire_exec_batch(measure_batch)
    keys = [space.key(p) for p, _ in at.history]
    revisited = {k for k in keys if keys.count(k) > 1}
    assert revisited  # the NM stage revisited a CSA-measured point
    # every delivered cost is the *measured* one — the optimistic half-price
    # bound never reached the optimizer or the history
    for p, c in at.history:
        assert c == pytest.approx(true_cost(p))
    # ...and the measured meta survived the stage transition
    for p, _ in at.history:
        if space.key(p) in revisited:
            meta = at.measurement_meta(p)
            assert meta is not None
            assert meta["pruned"] is None
            assert meta["repeats_spent"] == 3


def _bowl(point):
    """True cost of a two-knob search: a smooth bowl at (t1=16, t2=64), 1 ms
    at the optimum, with gaps between grid points far above the jitter."""
    return 1e-3 * (1.0 + math.log2(point["t1"] / 16.0) ** 2 + math.log2(point["t2"] / 64.0) ** 2)


def _race_bowl(policy, bounds):
    """One seeded CSA search of the bowl through a MeasureEngine whose
    repetitions return the true cost under a deterministic 0.1% jitter;
    returns (driver, engine, repetitions spent)."""
    space = SearchSpace([LogIntDim("t1", 4, 64), LogIntDim("t2", 16, 256)])
    at = Autotuning(space=space, ignore=0, search=CSA(2, num_opt=5, max_iter=4, seed=0),
                    cache=True)
    engine = MeasureEngine(policy)
    seen: dict = {}

    def rep_fn(p):
        def rep():
            key = (p["t1"], p["t2"])
            i = seen[key] = seen.get(key, -1) + 1
            v = ((p["t1"] * 1009 + p["t2"]) * 2654435761 + i * 40503 + 12345) & 0xFFFFFFFF
            return _bowl(p) * (1.0 + 1e-3 * ((v / 0xFFFFFFFF) * 2.0 - 1.0))

        return rep

    at.entire_exec_batch(lambda pts: engine.measure_round(
        [rep_fn(p) for p in pts], bounds=[0.9 * _bowl(p) for p in pts] if bounds else None))
    return at, engine, sum(seen.values()) + len(seen)


def test_adaptive_racing_halves_reps_and_keeps_fixed_best():
    """The same seeded search under adaptive racing (with a roofline bound
    at 90% of the true cost) spends at most half the repetitions of the
    fixed warmup-1 + 3-rep schedule, ends on the same best point, and never
    culls a candidate within the noise floor of the winner."""
    at_f, _, reps_fixed = _race_bowl(MeasurePolicy(mode="fixed", warmup=1, repeats=3), False)
    at_a, eng, reps_adaptive = _race_bowl(MeasurePolicy(mode="adaptive", warmup=1), True)
    assert reps_adaptive <= 0.5 * reps_fixed
    assert at_a.best_point == at_f.best_point
    assert eng.stats["culled"] > 0
    floor = eng._noise().floor(_bowl(at_a.best_point))
    for p, _ in at_a.history:
        meta = at_a.measurement_meta(p)
        if meta and meta.get("culled"):
            assert _bowl(p) - _bowl(at_a.best_point) > floor, p


def test_measurements_count_reps_actually_spent():
    space = SearchSpace([IntDim("k", 0, 3)])
    at = Autotuning(space=space, ignore=0,
                    optimizer=CSA(1, num_opt=3, max_iter=2, seed=0), cache=True)
    eng = MeasureEngine(MeasurePolicy(warmup=0, calibrate_reps=2))

    def measure_batch(points):
        return eng.measure_round(det_reps([1.0 + p["k"] for p in points]))

    at.entire_exec_batch(measure_batch)
    assert at.num_measurements == eng.stats["reps"]


# ------------------------------------------------------- RuntimeCost + record
def test_runtime_cost_records_raw_times():
    cost = RuntimeCost(warmup=1, repeats=3)
    c = cost(lambda: sum(range(200)))
    assert len(cost.last_times) == 3
    assert c == sorted(cost.last_times)[1]
    assert cost.last_std >= 0.0


def test_runtime_cost_reraises_interrupts():
    cost = RuntimeCost(warmup=0, repeats=2)
    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        if calls["n"] == 2:
            raise KeyboardInterrupt
        return 1

    with pytest.raises(KeyboardInterrupt):
        cost(fn)
    with pytest.raises(SystemExit):
        cost(lambda: (_ for _ in ()).throw(SystemExit(1)))


def test_tuning_record_confidence_roundtrip():
    from repro.tuning import make_key
    from repro.tuning.records import TuningRecord

    key = make_key("k", extra={"x": 1})
    rec = TuningRecord(key=key, point={"a": 1}, cost=0.5, cost_std=0.01,
                       repeats_spent=7)
    back = TuningRecord.from_json(rec.to_json())
    assert back.cost_std == pytest.approx(0.01)
    assert back.repeats_spent == 7
    # old records (fields absent) load with None
    blob = rec.to_json()
    del blob["cost_std"]
    del blob["repeats_spent"]
    old = TuningRecord.from_json(blob)
    assert old.cost_std is None and old.repeats_spent is None


def test_commit_near_tie_prefers_lower_variance():
    """A marginally 'better' new best inside the noise band must not clobber
    a lower-variance stored record it never re-measured."""
    from repro.tuning import TuningDB, make_key
    from repro.tuning.records import TuningRecord

    space = SearchSpace([IntDim("k", 0, 63)])
    key = make_key("near_tie", space=space, extra={"case": 1})
    db = TuningDB(None)
    stored = {"k": 50}
    db.put(TuningRecord(key=key, point=dict(stored), cost=1.000, cost_std=0.002,
                        repeats_spent=7))
    at = Autotuning(space=space, ignore=0,
                    optimizer=CSA(1, num_opt=3, max_iter=2, seed=0),
                    cache=True, db=db, key=key, warm_start=False)

    def measure_batch(points):
        # every visited point "measures" 0.999 with high variance: a lucky
        # near-tie one noise-width under the stored best
        return [MeasureResult(cost=0.999, cost_std=0.05, repeats_spent=1)
                for _ in points]

    at.entire_exec_batch(measure_batch)
    # the guard only applies to a stored point this run never re-measured
    assert all(p != stored for p, _ in at.history)
    kept = db.get(key)
    assert kept.point == stored and kept.cost == pytest.approx(1.000)
    # a decisive improvement (beyond the noise band) still wins
    at2 = Autotuning(space=space, ignore=0,
                     optimizer=CSA(1, num_opt=3, max_iter=2, seed=1),
                     cache=True, db=db, key=key, warm_start=False)

    def measure_batch2(points):
        return [MeasureResult(cost=0.5, cost_std=0.05, repeats_spent=3)
                for _ in points]

    at2.entire_exec_batch(measure_batch2)
    assert db.get(key).cost == pytest.approx(0.5)


def test_commit_single_rep_record_never_blocks_refresh():
    """A stored single-rep record's std of 0.0 is *unknown* confidence, not
    perfect confidence — it must not survive as 'lower variance' against a
    fully-measured near-tie."""
    from repro.tuning import TuningDB, make_key
    from repro.tuning.records import TuningRecord

    space = SearchSpace([IntDim("k", 0, 63)])
    key = make_key("near_tie", space=space, extra={"case": "single_rep"})
    db = TuningDB(None)
    db.put(TuningRecord(key=key, point={"k": 50}, cost=1.000, cost_std=0.0,
                        repeats_spent=1))
    at = Autotuning(space=space, ignore=0,
                    optimizer=CSA(1, num_opt=3, max_iter=2, seed=0),
                    cache=True, db=db, key=key, warm_start=False)

    def measure_batch(points):
        return [MeasureResult(cost=0.999, cost_std=0.01, repeats_spent=7)
                for _ in points]

    at.entire_exec_batch(measure_batch)
    assert all(p != {"k": 50} for p, _ in at.history)
    assert db.get(key).cost == pytest.approx(0.999)  # the fluke is replaced


def test_record_from_carries_measurement_confidence():
    from repro.tuning import make_key
    from repro.tuning.warm_start import record_from

    space = _bowl_space()
    at = Autotuning(space=space, ignore=0,
                    optimizer=CSA(1, num_opt=4, max_iter=3, seed=0), cache=True)
    eng = MeasureEngine(MeasurePolicy(warmup=0, calibrate_reps=2))

    def measure_batch(points):
        return eng.measure_round(det_reps([_bowl_cost(p) for p in points],
                                          jitter=1e-4))

    at.entire_exec_batch(measure_batch)
    rec = record_from(at, make_key("conf", space=space))
    assert rec.repeats_spent is not None and rec.repeats_spent >= 1
    assert rec.cost_std is not None and rec.cost_std >= 0.0


# --------------------------------------------------------------- online mode
def _online_tuner(measure, epsilon=1.0, seed=0):
    from repro.runtime.online import OnlineTuner

    space = SearchSpace([IntDim("k", 0, 5)])
    at = Autotuning(space=space, ignore=0,
                    optimizer=CSA(1, num_opt=3, max_iter=3, seed=seed),
                    cache=True)
    return OnlineTuner(at, epsilon=epsilon, measure=measure)


def _drive_online(tuner, cost_of, max_requests=10_000):
    """Serve synthetic explore traffic until the search converges; returns
    the number of requests spent."""
    n = 0
    while not tuner.finished and n < max_requests:
        d = tuner.begin(_force_explore=True)
        assert d.kind == "explore"
        tuner.observe(d, cost_of(d.point))
        n += 1
    assert tuner.finished
    return n


def test_online_adaptive_culls_and_converges_in_fewer_requests():
    """Dominated candidates are decided after one live request; the same
    search under a fixed 3-rep policy pays the full schedule every time."""
    cost_of = lambda p: 1.0 + p["k"]  # k=0 dominates, others dominated

    adaptive = _online_tuner(MeasurePolicy(warmup=0))
    n_adaptive = _drive_online(adaptive, cost_of)
    fixed = _online_tuner(MeasurePolicy(mode="fixed", repeats=3))
    n_fixed = _drive_online(fixed, cost_of)

    assert adaptive.at.best_point == fixed.at.best_point == {"k": 0}
    assert n_adaptive < n_fixed
    assert adaptive.stats_["culled_explores"] > 0
    # requests = repetitions: every explore request was charged to exactly
    # one candidate's measurement (cache-answered revisits are free, so
    # num_evals can exceed the candidates actually served)
    assert adaptive.stats_["explores"] == n_adaptive
    assert adaptive.stats_["explore_candidates"] <= adaptive.at.num_evals
    assert fixed.stats_["explores"] == n_fixed
    # the fixed schedule pays repeats per decided candidate
    assert n_fixed >= 3 * fixed.stats_["explore_candidates"]


def test_online_epsilon_accounting_with_fractional_explores():
    """ε rations explore *requests* (repetitions), so culled candidates
    consume a fraction of the budget a full ladder evaluation would."""
    tuner = _online_tuner(MeasurePolicy(warmup=0), epsilon=0.25)
    cost_of = lambda p: 1.0 + p["k"]
    calls = 0
    while not tuner.finished and calls < 4000:
        d = tuner.begin()
        tuner.observe(d, cost_of(d.point) if d.kind == "explore" else 1.0)
        calls += 1
    assert tuner.finished
    s = tuner.stats_
    assert s["explores"] + s["exploits"] == calls
    # the ε-credit ledger holds at every prefix by construction; check the
    # aggregate explicitly
    assert s["explores"] <= 0.25 * calls + 1
    assert s["culled_explores"] > 0


def test_online_legacy_single_rep_unchanged():
    """measure=None keeps the classic one-request-per-candidate protocol."""
    tuner = _online_tuner(None)
    n = _drive_online(tuner, lambda p: 1.0 + p["k"])
    # one request == one decided candidate (cache-answered revisits aside)
    assert tuner.stats_["explore_candidates"] == n
    assert tuner.stats_["culled_explores"] == 0


# ----------------------------------------------------------- tune_call wiring
@pytest.fixture
def measure_probe_kernel():
    import jax.numpy as jnp

    from repro.kernels.autotuned import _REGISTRY, KernelSpec, register

    def probe(x, *, t1, t2, interpret=False):
        val = (jnp.log2(t1 / 16.0)) ** 2 + (jnp.log2(t2 / 64.0)) ** 2
        return x.sum() * 0.0 + val + 0.5

    name = "_measure_probe"
    register(
        KernelSpec(
            name=name,
            fn=probe,
            space=lambda x: SearchSpace(
                [LogIntDim("t1", 4, 64), LogIntDim("t2", 16, 256)]
            ),
            defaults=lambda x: {"t1": 16, "t2": 64},
        )
    )
    yield name
    _REGISTRY.pop(name, None)


def det_cost(ex, *args):
    return float(np.asarray(ex(*args)))


def test_tune_call_fixed_reproduces_sequential_best(measure_probe_kernel):
    """--measure fixed is the trajectory-pinned policy: same committed best
    point as the pre-engine sequential reference on a deterministic cost."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.autotuned import exec_cache, get_spec, tune_call
    from repro.tuning import TuningDB, make_key

    x = jnp.ones((4, 4))
    spec = get_spec(measure_probe_kernel)
    space = spec.space(x)
    key = make_key(measure_probe_kernel, args=(x,), space=space,
                   extra={"interpret": True})
    db_s = TuningDB(None)

    def measure(*knob_values):
        knobs = dict(zip(space.names, knob_values))
        fn = jax.jit(lambda *xs: spec.fn(*xs, **knobs, interpret=True))
        return det_cost(fn, x)

    at = Autotuning(space=space, ignore=0,
                    optimizer=CSA(2, num_opt=3, max_iter=3, seed=0),
                    cache=True, db=db_s, key=key)
    at.entire_exec(measure)
    at.commit()
    rec_seq = db_s.get(key)

    exec_cache().clear()
    stats: dict = {}
    rec_fixed = tune_call(measure_probe_kernel, x, db=TuningDB(None),
                          interpret=True, num_opt=3, max_iter=3, seed=0,
                          jobs=2, cost_fn=det_cost, measure="fixed",
                          measure_stats=stats)
    rec_adaptive = tune_call(measure_probe_kernel, x, db=TuningDB(None),
                             interpret=True, num_opt=3, max_iter=3, seed=0,
                             jobs=2, cost_fn=det_cost, measure="adaptive")
    assert rec_seq is not None
    assert rec_fixed.point == rec_seq.point and rec_fixed.cost == rec_seq.cost
    assert stats["mode"] == "fixed"
    # the adaptive policy finds the same best on a deterministic cost
    assert rec_adaptive.point == rec_seq.point


def test_tune_call_adaptive_reports_stats(measure_probe_kernel):
    import jax.numpy as jnp

    from repro.kernels.autotuned import tune_call
    from repro.tuning import TuningDB

    x = jnp.ones((4, 4))
    stats: dict = {}
    rec = tune_call(measure_probe_kernel, x, db=TuningDB(None), interpret=True,
                    num_opt=4, max_iter=3, seed=0, jobs=2, cost_fn=det_cost,
                    measure="adaptive", measure_stats=stats)
    assert rec is not None
    assert stats["mode"] == "adaptive"
    assert stats["reps"] >= stats["measured"] >= 1
    assert stats["culled"] >= 1  # dominated knobs raced out
    assert rec.repeats_spent is not None and rec.repeats_spent >= 1


def test_pretune_measure_fixed_flag(tmp_path, capsys):
    """pretune --measure fixed runs the classic schedule end to end on one
    tiny grid case and commits a record."""
    from repro.tuning import TuningDB
    from repro.tuning.pretune import main as pretune_main

    db_path = str(tmp_path / "fixed.json")
    rc = pretune_main([
        "--db", db_path, "--smoke", "--only", "matmul/64*",
        "--measure", "fixed", "--num-opt", "2", "--max-iter", "1",
    ])
    out = capsys.readouterr().out
    assert rc == 0
    assert "matmul/64x64x64: best=" in out
    db = TuningDB(db_path)
    assert len(db) == 1
    rec = next(iter(db.records()))
    assert rec.cost_std is not None  # fixed RuntimeCost carries confidence
