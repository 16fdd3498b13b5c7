"""Resilience layer: guarded calls, fault injection, circuit breaking,
quarantine, and crash/resume of the pretune sweep.

The fault-injection tests (marked ``chaos``) run the *real* execution paths
under deterministic fault plans — hangs, transient storms, hard crashes,
mid-run kills — and assert the recovery, not the injection.  The CI chaos
lane re-runs them with a straggler plan injected through ``REPRO_FAULT_PLAN``
on top.
"""
import math
import os
import time

import numpy as np
import pytest

from repro.core import (
    CircuitBreaker,
    FaultPolicy,
    GuardTimeout,
    MeasureEngine,
    MeasurePolicy,
    Quarantine,
    SandboxCrash,
    compile_fanout,
    deterministic_backoff,
    guarded_call,
    is_transient_failure,
    sandboxed_probe,
)
from repro.testing import FaultPlan, FaultSpec, InjectedCrash, parse_plan, tear_file


@pytest.fixture(autouse=True)
def _fresh_fault_plans():
    """Env-configured plans are cached per env value with live counters;
    tests must not inherit a sibling's exhausted plan."""
    from repro.testing import faults

    faults._active.clear()
    yield
    faults._active.clear()


# ----------------------------------------------------------- guarded_call
def test_guarded_call_timeout_fires_on_hang():
    with pytest.raises(GuardTimeout):
        guarded_call(lambda: time.sleep(0.5), timeout=0.05, label="hang")


def test_guarded_call_timeout_never_retried_in_band():
    calls = {"n": 0}

    def hang():
        calls["n"] += 1
        time.sleep(0.5)

    with pytest.raises(GuardTimeout):
        guarded_call(hang, timeout=0.05, retries=5, backoff=0.0)
    assert calls["n"] == 1  # each retry would cost another full deadline


def test_guarded_call_transient_retried_exactly_with_backoff():
    calls = {"n": 0}
    sleeps: list = []
    retries_seen: list = []

    def flaky():
        calls["n"] += 1
        if calls["n"] <= 2:
            raise RuntimeError("RESOURCE_EXHAUSTED: injected")
        return 42

    out = guarded_call(
        flaky,
        retries=2,
        backoff=0.01,
        backoff_mult=2.0,
        jitter=0.25,
        label="tok",
        on_retry=lambda a, e, d: retries_seen.append((a, d)),
        sleep=sleeps.append,
    )
    assert out == 42
    assert calls["n"] == 3  # transient-twice-then-succeed: exactly 2 retries
    # the backoff schedule is exponential and deterministically jittered
    expect = [deterministic_backoff(a, 0.01, 2.0, 0.25, "tok") for a in (0, 1)]
    assert sleeps == expect
    assert expect[1] > expect[0]
    assert [a for a, _ in retries_seen] == [0, 1]


def test_guarded_call_permanent_failure_not_retried():
    calls = {"n": 0}

    def bug():
        calls["n"] += 1
        raise ValueError("not a resource problem")

    with pytest.raises(ValueError):
        guarded_call(bug, retries=5, backoff=0.0)
    assert calls["n"] == 1


def test_guarded_call_retries_exhausted_raises_last():
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        guarded_call(
            lambda: (_ for _ in ()).throw(RuntimeError("RESOURCE_EXHAUSTED: x")),
            retries=2,
            backoff=0.0,
            sleep=lambda d: None,
        )


def test_deterministic_backoff_reproducible_and_desynchronized():
    a1 = deterministic_backoff(1, 0.05, 2.0, 0.25, "shard0")
    assert a1 == deterministic_backoff(1, 0.05, 2.0, 0.25, "shard0")
    assert a1 != deterministic_backoff(1, 0.05, 2.0, 0.25, "shard1")
    base = 0.05 * 2.0**1
    assert base <= a1 <= base * 1.25  # jitter only ever stretches


def test_is_transient_failure_classes():
    assert is_transient_failure(RuntimeError("RESOURCE_EXHAUSTED: oom"))
    assert is_transient_failure(GuardTimeout("deadline"))
    assert not is_transient_failure(SandboxCrash("died", exitcode=-11))
    assert not is_transient_failure(ValueError("block size mismatch"))


# -------------------------------------------------------- sandboxed_probe
def test_sandboxed_probe_contains_hard_crash():
    # a clean callable survives its probe
    assert sandboxed_probe(lambda: 1 + 1, timeout=30.0)
    # an ordinary Python exception is NOT a crash: the real in-process
    # build must get to raise (and classify) it
    assert sandboxed_probe(lambda: 1 / 0, timeout=30.0)

    # a hard exit is contained in the child and surfaces as SandboxCrash
    def die():
        os._exit(3)

    with pytest.raises(SandboxCrash) as ei:
        sandboxed_probe(die, timeout=30.0)
    assert ei.value.exitcode == 3


# -------------------------------------------------------------- quarantine
def test_quarantine_threshold_and_recovery():
    q = Quarantine(max_failures=2)
    assert not q.note_failure("k")
    assert "k" not in q
    assert q.note_failure("k")
    assert "k" in q
    q.note_success("k")  # a success clears the strike count
    assert "k" not in q
    assert q.stats()["max_failures"] == 2


# --------------------------------------------------------- circuit breaker
def test_circuit_breaker_walk():
    b = CircuitBreaker(threshold=2, cooldown=3)
    assert b.state == CircuitBreaker.CLOSED and b.allow()
    b.record_failure()
    assert b.state == CircuitBreaker.CLOSED  # one below threshold
    b.record_failure()
    assert b.state == CircuitBreaker.OPEN and b.opens == 1
    # cooldown is counted in denied calls, not wall time
    assert not b.allow()
    assert not b.allow()
    assert b.denied == 2
    assert b.allow()  # third tick: half-open, probe granted
    assert b.state == CircuitBreaker.HALF_OPEN and b.probes == 1
    b.record_failure()  # failed probe re-trips immediately
    assert b.state == CircuitBreaker.OPEN and b.opens == 2
    assert not b.allow() and not b.allow()
    assert b.allow()  # probe again
    b.record_success()
    assert b.state == CircuitBreaker.CLOSED
    assert b.allow() and b.stats()["opens"] == 2


def test_circuit_breaker_success_resets_strike_count():
    b = CircuitBreaker(threshold=2, cooldown=2)
    b.record_failure()
    b.record_success()
    b.record_failure()
    assert b.state == CircuitBreaker.CLOSED  # never two consecutive


# ------------------------------------------------------------- fault plans
@pytest.mark.chaos
def test_fault_plan_matching_and_counters():
    plan = FaultPlan(
        [
            FaultSpec(kind="transient", site="cost", match={"bm": 32}, times=2),
            FaultSpec(kind="crash", site="build", calls=(3,)),
        ]
    )
    plan.fire("cost", key={"bm": 64, "bn": 32})  # no match: bm differs
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        plan.fire("cost", key={"bm": 32, "bn": 64})  # dict-subset match
    with pytest.raises(RuntimeError):
        plan.fire("cost", key={"bm": 32, "bn": 32})
    plan.fire("cost", key={"bm": 32})  # times=2 exhausted: passes through
    plan.fire("build")
    plan.fire("build")
    with pytest.raises(InjectedCrash):
        plan.fire("build")  # 3rd build call
    assert plan.count("cost") == 2 and plan.count("build") == 1
    assert plan.count() == 3


@pytest.mark.chaos
def test_fault_plan_parse_and_env(monkeypatch):
    from repro.testing import active_plan
    from repro.testing.faults import ENV_FAULT_PLAN

    plan = parse_plan('[{"site": "tune", "kind": "kill", "calls": [2]}]')
    assert plan.specs[0].kind == "kill" and plan.specs[0].calls == (2,)
    monkeypatch.setenv(ENV_FAULT_PLAN, '[{"kind": "slow", "seconds": 0.0}]')
    p1 = active_plan()
    assert p1 is active_plan()  # cached: counters persist across tune_calls
    monkeypatch.delenv(ENV_FAULT_PLAN)
    assert active_plan() is None


def test_fault_plan_string_match_and_kill():
    plan = FaultPlan([FaultSpec(kind="kill", site="tune", match="matmul")])
    plan.fire("tune", key="flash_attention")  # substring miss
    with pytest.raises(SystemExit):
        plan.fire("tune", key="matmul")


# --------------------------------------------------------- measure engine
def test_measure_engine_timeout_charges_inf_run_survives():
    policy = MeasurePolicy(mode="fixed", warmup=0, repeats=1)
    eng = MeasureEngine(policy, guard=FaultPolicy(measure_timeout=0.05))
    out = eng.measure_round([lambda: time.sleep(0.5), lambda: 0.010])
    assert math.isinf(out[0].cost)
    assert out[1].cost == pytest.approx(0.010)
    assert eng.stats["timeouts"] == 1 and eng.stats["failed"] == 1


def test_measure_engine_retries_transients_in_place():
    calls = {"n": 0}

    def flaky_rep():
        calls["n"] += 1
        if calls["n"] <= 2:
            raise RuntimeError("RESOURCE_EXHAUSTED: injected")
        return 0.010

    policy = MeasurePolicy(mode="fixed", warmup=0, repeats=1)
    eng = MeasureEngine(policy, guard=FaultPolicy(retries=2, backoff=0.0))
    out = eng.measure_round([flaky_rep])
    assert out[0].cost == pytest.approx(0.010)
    assert eng.stats["retried"] == 2 and eng.stats["failed"] == 0


# --------------------------------------------------------- compile fanout
def test_compile_fanout_deadline_charges_unfinished():
    def quick():
        return "ok"

    def slow():
        time.sleep(1.0)
        return "late"

    out = compile_fanout(
        [("a", quick), ("b", slow), ("c", slow)], jobs=2, deadline=0.2
    )
    assert out[0] == "ok"
    assert isinstance(out[1], GuardTimeout) and isinstance(out[2], GuardTimeout)


def test_compile_fanout_fatal_raises_first_poison():
    def poison():
        raise TypeError("unexpected kwarg 'bm'")

    def fine():
        return "ok"

    with pytest.raises(TypeError):
        compile_fanout(
            [("a", poison), ("b", fine)],
            jobs=2,
            fatal=lambda e: isinstance(e, TypeError),
        )
    # without the predicate the classic returned-not-raised contract holds
    out = compile_fanout([("a", poison), ("b", fine)], jobs=2)
    assert isinstance(out[0], TypeError) and out[1] == "ok"


# -------------------------------------------- tune_call under a fault plan
def _matmul_args(n=512):
    import jax.numpy as jnp

    # 512 keeps this grid (bm in {32, 256}, bn/bk in {128, 256}) off every
    # other test's shapes, so the process executable cache is cold and
    # build-site faults actually reach the builds
    return jnp.ones((n, n), jnp.float32), jnp.ones((n, n), jnp.float32)


@pytest.mark.chaos
@pytest.mark.slow
def test_tune_call_completes_under_fault_plan():
    """The acceptance scenario: a hang + two transients + one hard crash
    across candidates must not kill the run, and with deterministic costs
    the faulted search converges to the fault-free best point."""
    from repro.tuning import TuningDB
    from repro.tuning.pretune import _analytic_cost_fn
    from repro.kernels.autotuned import tune_call

    a, b = _matmul_args()
    cost_fn = _analytic_cost_fn()
    plan = FaultPlan(
        [
            FaultSpec(kind="hang", site="cost",
                      match={"bm": 32, "bn": 128, "bk": 256}, seconds=0.3),
            FaultSpec(kind="transient", site="cost",
                      match={"bm": 256, "bn": 128, "bk": 128}, times=2),
            FaultSpec(kind="crash", site="build",
                      match={"bm": 32, "bn": 256, "bk": 128}, times=1),
        ]
    )
    # the faulted run goes FIRST: its builds are cache-cold, so the
    # build-site crash genuinely reaches a build
    ms: dict = {}
    rec_faulted = tune_call(
        "matmul", a, b, db=TuningDB(path=None), interpret=True,
        strategy="grid", cost_fn=cost_fn, warm_start=False, jobs=1,
        measure_stats=ms,
        fault_policy=FaultPolicy(measure_timeout=0.05, retries=2, backoff=0.001),
        fault_plan=plan,
    )
    rec_clean = tune_call(
        "matmul", a, b, db=TuningDB(path=None), interpret=True,
        strategy="grid", cost_fn=cost_fn, warm_start=False, jobs=1,
        fault_plan=FaultPlan([]),  # isolate from any env-injected plan
    )
    assert plan.count() >= 4  # hang + 2 transients + crash all fired
    assert ms["timeouts"] == 1  # the hang was charged, not waited out
    assert ms["retried"] >= 2  # the transient candidate was retried in place
    assert rec_faulted is not None and rec_clean is not None
    assert rec_faulted.point == rec_clean.point  # same best despite the storm
    assert rec_faulted.cost == pytest.approx(rec_clean.cost)


@pytest.mark.chaos
def test_tune_call_quarantine_skips_repeat_offender():
    """A candidate that keeps failing stops being offered builds at all:
    with max_failures=1 the first failure quarantines it, and later rounds
    (the grid revisits nothing, so force revisits via two tune_calls on the
    same Quarantine-scoped search) charge it inf without a measurement."""
    from repro.tuning import TuningDB
    from repro.kernels.autotuned import tune_call

    a, b = _matmul_args(96)  # pow2_floor(96)=32: single-point grid elsewhere
    costs = {"calls": 0}

    def cost_fn(ex, *args):
        costs["calls"] += 1
        raise RuntimeError("vmem exceeded: always-illegal candidate")

    ms: dict = {}
    rec = tune_call(
        "matmul", a, b, db=TuningDB(path=None), interpret=True,
        strategy="grid", cost_fn=cost_fn, warm_start=False, jobs=1,
        measure_stats=ms, fault_plan=FaultPlan([]),
        fault_policy=FaultPolicy(max_failures=1, retries=0),
    )
    assert rec is None  # every candidate failed: nothing stored
    assert ms["quarantined"] >= 1


# ----------------------------------------------- breaker in the OnlineTuner
def test_online_tuner_breaker_gates_and_recovers():
    from repro.core import Autotuning, IntDim, SearchSpace
    from repro.runtime.online import EXPLOIT, EXPLORE, OnlineTuner

    space = SearchSpace([IntDim("x", 0, 7)])
    at = Autotuning(space=space, num_opt=2, max_iter=4, seed=0, cache=False)
    t = OnlineTuner(
        at, epsilon=1.0, default_point={"x": 0},
        breaker={"threshold": 2, "cooldown": 3},
    )
    # two failing explores trip the breaker
    for _ in range(2):
        d = t.begin()
        assert d.kind == EXPLORE
        t.observe(d, np.inf)
    assert t.breaker.state == CircuitBreaker.OPEN
    # while open: incumbent served, no e-credits burned, cooldown ticks
    for _ in range(2):
        d = t.begin()
        assert d.kind == EXPLOIT
    assert t.stats_["breaker_denied"] == 2
    # cooldown lapsed: half-open probe explores again
    d = t.begin()
    assert d.kind == EXPLORE and t.breaker.state == CircuitBreaker.HALF_OPEN
    t.observe(d, 1.0)  # healthy probe closes the breaker
    assert t.breaker.state == CircuitBreaker.CLOSED
    d = t.begin()
    assert d.kind == EXPLORE  # exploration resumed
    t.observe(d, 1.0)
    assert t.stats()["breaker"]["opens"] == 1


def test_online_tuner_breaker_failed_probe_reopens():
    from repro.core import Autotuning, IntDim, SearchSpace
    from repro.runtime.online import EXPLOIT, EXPLORE, OnlineTuner

    space = SearchSpace([IntDim("x", 0, 7)])
    at = Autotuning(space=space, num_opt=2, max_iter=4, seed=0, cache=False)
    t = OnlineTuner(
        at, epsilon=1.0, default_point={"x": 0},
        breaker={"threshold": 1, "cooldown": 2},
    )
    d = t.begin()
    t.observe(d, np.inf)  # threshold=1: open immediately
    assert t.breaker.state == CircuitBreaker.OPEN
    assert t.begin().kind == EXPLOIT
    d = t.begin()  # second tick: half-open probe
    assert d.kind == EXPLORE
    t.observe(d, np.inf)  # probe fails: re-open for another cooldown
    assert t.breaker.state == CircuitBreaker.OPEN and t.breaker.opens == 2
    assert t.begin().kind == EXPLOIT


def test_autotuning_skip_reasons_tagged():
    from repro.core import Autotuning, IntDim, SearchSpace

    at = Autotuning(
        space=SearchSpace([IntDim("x", 0, 7)]), num_opt=2, max_iter=4, cache=False
    )
    at.skip(np.inf, reason="build-failed")
    at.skip(np.inf, reason="build-failed")
    at.skip(np.inf, reason="quarantined")
    assert at.skip_reasons == {"build-failed": 2, "quarantined": 1}


# ------------------------------------------------------------- run journal
def test_run_journal_roundtrip_and_torn_write(tmp_path):
    from repro.tuning import RunJournal, TuningDB
    from repro.tuning.records import TuningRecord
    from repro.tuning import make_key
    from repro.core import IntDim, SearchSpace

    space = SearchSpace([IntDim("x", 0, 7)])
    k1 = make_key("demo", args=(), space=space, extra={"case": 1})
    k2 = make_key("demo", args=(), space=space, extra={"case": 2})
    k3 = make_key("demo", args=(), space=space, extra={"case": 3})
    rec = TuningRecord(key=k1, point={"x": 3}, cost=1.25, evals=8, source="test")

    j = RunJournal(str(tmp_path / "db.json.journal"))
    j.start(k1)
    j.commit(k1, rec)
    j.start(k2)
    j.failed(k2, RuntimeError("every candidate failed"))
    j.start(k3)  # interrupted: no verdict before the "kill"
    s = j.summary()
    assert set(s["committed"]) == {k1.encode()}
    assert s["failed"] == {k2.encode()}
    assert s["interrupted"] == {k3.encode()}

    # the journal alone reconstructs a DB of the committed work
    db = j.to_db()
    assert len(db) == 1 and db.get(k1).point == {"x": 3}
    assert RunJournal.is_journal(j.path)
    assert not RunJournal.is_journal(__file__)

    # a torn trailing line (power loss mid-append) loses only the tail
    j2 = RunJournal(str(tmp_path / "torn.journal"))
    j2.start(k1)
    j2.commit(k1, rec)
    size_before_tail = os.path.getsize(j2.path)
    j2.start(k2)
    tear_file(j2.path, keep_bytes=size_before_tail + 10)
    s2 = j2.summary()
    assert set(s2["committed"]) == {k1.encode()}
    assert s2["interrupted"] == set()  # the torn start never happened


@pytest.mark.chaos
@pytest.mark.slow
def test_pretune_kill_resume_matches_uninterrupted(tmp_path, monkeypatch):
    """A shard killed mid-sweep, resumed with --resume, must (a) re-measure
    zero completed cases and (b) end with a DB that ``db diff --costs``
    reports identical to an uninterrupted run's."""
    from repro.testing.faults import ENV_FAULT_PLAN
    from repro.tune import main as tune_main
    from repro.tuning import RunJournal

    monkeypatch.chdir(tmp_path)
    common = [
        "pretune", "--smoke", "--cost", "analytic", "--no-warm-start",
        "--kernel", "matmul", "--jobs", "1",
    ]
    monkeypatch.delenv(ENV_FAULT_PLAN, raising=False)
    assert tune_main(common + ["--db", "ref.json"]) == 0

    # kill the worker at its second tune_call (mid-sweep)
    monkeypatch.setenv(
        ENV_FAULT_PLAN, '[{"site": "tune", "kind": "kill", "calls": [2]}]'
    )
    with pytest.raises(SystemExit):
        tune_main(common + ["--db", "k.json"])
    monkeypatch.delenv(ENV_FAULT_PLAN)

    j = RunJournal("k.json.journal")
    s = j.summary()
    assert len(s["committed"]) == 1 and len(s["interrupted"]) == 1
    committed_before = set(s["committed"])

    # the journal's committed records already merge like a shard DB
    assert tune_main(["db", "merge", "--out", "partial.json", "k.json.journal"]) == 0
    assert tune_main(["db", "diff", "partial.json", "k.json"]) == 0

    assert tune_main(common + ["--db", "k.json", "--resume"]) == 0

    # zero re-measurement: after the resume marker, no completed case starts
    events = j.events()
    resume_at = max(i for i, ev in enumerate(events) if ev["event"] == "resume")
    restarted = {
        ev["key"] for ev in events[resume_at:] if ev["event"] == "start"
    }
    assert restarted.isdisjoint(committed_before)
    # and the resumed DB is byte-equivalent to the uninterrupted one
    assert tune_main(["db", "diff", "--costs", "ref.json", "k.json"]) == 0


@pytest.mark.chaos
def test_chaos_lane_env_plan_reaches_tune_call(monkeypatch):
    """With REPRO_FAULT_PLAN set (how the CI chaos lane injects), a plain
    tune_call picks the plan up with zero plumbing."""
    from repro.testing.faults import ENV_FAULT_PLAN, active_plan
    from repro.tuning import TuningDB
    from repro.tuning.pretune import _analytic_cost_fn
    from repro.kernels.autotuned import tune_call

    monkeypatch.setenv(
        ENV_FAULT_PLAN,
        '[{"site": "cost", "kind": "slow", "seconds": 0.0001, "times": 1000}]',
    )
    a, b = _matmul_args(64)
    rec = tune_call(
        "matmul", a, b, db=TuningDB(path=None), interpret=True,
        strategy="grid", cost_fn=_analytic_cost_fn(), warm_start=False, jobs=1,
    )
    assert rec is not None
    assert active_plan().count("cost") > 0  # the stragglers actually fired


def test_sandboxed_probe_refuses_accelerator_backend(monkeypatch):
    """A forked child cannot use the device its parent holds: off the CPU
    backend the probe refuses with a clear error instead of hanging."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="forked child"):
        sandboxed_probe(lambda: 1 + 1, timeout=30.0)
