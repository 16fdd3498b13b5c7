"""Composable search strategies: Pipeline / Portfolio combinators, the
string-spec parser, Autotuning wiring, and strategy provenance on persisted
records.  Everything here is deterministic (seeded optimizers, analytic
costs)."""
import json

import numpy as np
import pytest

from repro.core import (
    CSA,
    Autotuning,
    GridSearch,
    IntDim,
    NelderMead,
    Pipeline,
    Portfolio,
    RandomSearch,
    SearchSpace,
    TunedStep,
    make_strategy,
    strategy_label,
)
from repro.core.measure import NoiseEstimate
from repro.tuning import TuningDB, TuningRecord, make_key


def sphere(z):
    return float(np.sum(np.asarray(z) ** 2))


def rastrigin(z):
    x = np.asarray(z) * 2.0
    return float(10 * x.size + np.sum(x**2 - 10 * np.cos(2 * np.pi * x)))


def rosenbrock(z):
    x = np.asarray(z) * 2.0
    return float(np.sum(100 * (x[1:] - x[:-1] ** 2) ** 2 + (1 - x[:-1]) ** 2))


#: deterministic cost models on the unit box, each with its optimum at 0
COST_MODELS = {"sphere": sphere, "rastrigin": rastrigin, "rosenbrock": rosenbrock}


def drive(opt, fn):
    """Run a strategy to completion via ask/tell; returns total tells."""
    n = 0
    while not opt.is_end():
        batch = opt.ask()
        if not batch:
            break
        opt.tell([fn(z) for z in batch])
        n += len(batch)
    return n


# ------------------------------------------------------------------ pipeline
def test_pipeline_budget_split_is_exact():
    """Total tells == budget, split across stages by budget_fracs; the last
    batch is truncated to the remaining allowance."""
    for budget, fracs in [(40, (0.5, 0.5)), (37, (0.7, 0.3)), (23, None)]:
        p = Pipeline(
            [CSA(2, num_opt=4, max_iter=100, seed=0),
             NelderMead(2, error=0.0, max_iter=1000, seed=0)],
            fracs, budget=budget,
        )
        assert drive(p, sphere) == budget
        assert p.spent == budget
        assert p.is_end()


def test_pipeline_second_stage_seeded_at_first_stage_best():
    """The NM stage starts from a simplex built around CSA's best (the
    paper's hybrid handoff), so its first asked vertex IS the incumbent."""
    csa = CSA(2, num_opt=4, max_iter=5, seed=3)
    nm = NelderMead(2, error=0.0, max_iter=50, seed=3)
    p = Pipeline([csa, nm], (0.5, 0.5), budget=40)
    incumbent = None
    while not p.is_end():
        batch = p.ask()
        if not batch:
            break
        if p.stage_index == 1 and incumbent is None:
            incumbent = batch[0]  # first NM vertex
            np.testing.assert_allclose(incumbent, p.best_solution)
        p.tell([sphere(z) for z in batch])
    assert incumbent is not None


def test_pipeline_stage_budget_rolls_forward_on_early_convergence():
    """A stage that converges early donates its unspent share downstream."""
    # grid of 4 points finishes long before its 0.8 share of 40
    p = Pipeline(
        [GridSearch(1, points_per_dim=4),
         NelderMead(1, error=0.0, max_iter=1000, seed=0)],
        (0.8, 0.2), budget=40,
    )
    assert drive(p, sphere) == 40  # 4 grid tells + 36 NM tells
    assert p.stage_index == 1


def test_pipeline_truncated_round_not_fed_to_stage():
    """A truncated boundary batch updates the pipeline incumbent but is not
    delivered to the stage optimizer (its round contract stays whole)."""
    csa = CSA(2, num_opt=4, max_iter=100, seed=0)
    nm = NelderMead(2, error=0.0, max_iter=1000, seed=0)
    p = Pipeline([csa, nm], (0.5, 0.5), budget=22)  # stage-1 boundary at 11
    seen = []
    while not p.is_end():
        batch = p.ask()
        if not batch:
            break
        seen.append((p.stage_index, len(batch)))
        p.tell([sphere(z) for z in batch])
    # CSA emits rounds of 4; its 11-tell allowance ends in a truncated 3-batch
    stage0 = [n for si, n in seen if si == 0]
    assert stage0 == [4, 4, 3]
    # the stage optimizer only consumed the two full rounds
    assert csa.iteration == 3
    assert p.spent == 22


def test_pipeline_best_includes_truncated_measurements():
    p = Pipeline(
        [RandomSearch(1, max_iter=100, seed=0),
         NelderMead(1, error=0.0, max_iter=100, seed=0)],
        (0.5, 0.5), budget=10,
    )
    costs = iter([5.0, 4.0, 3.0, 2.0, 1.0, 0.5, 0.4, 0.3, 0.2, -7.0])
    drive(p, lambda z: next(costs))
    assert p.best_cost == -7.0


def test_pipeline_reset_level0_restarts_current_stage_only():
    p = Pipeline(
        [CSA(1, num_opt=4, max_iter=4, seed=0),
         NelderMead(1, error=0.0, max_iter=100, seed=0)],
        (0.5, 0.5), budget=32,
    )
    drive(p, lambda z: sphere(z) + 1.0)
    assert p.is_end() and p.stage_index == 1
    best = p.best_cost
    p.reset(0)
    assert not p.is_end()
    assert p.stage_index == 1  # the *current* stage restarts, not the pipeline
    assert p.best_cost == best  # level 0 retains found solutions
    assert drive(p, lambda z: sphere(z) + 1.0) > 0  # fresh stage allowance


def test_pipeline_reset_level1_restarts_warm_at_incumbent():
    csa = CSA(2, num_opt=4, max_iter=4, seed=1)
    nm = NelderMead(2, error=0.0, max_iter=100, seed=1)
    p = Pipeline([csa, nm], (0.5, 0.5), budget=32)
    drive(p, sphere)
    incumbent = p.best_solution
    p.reset(1)
    assert not p.is_end()
    assert p.stage_index == 0  # the whole pipeline restarts...
    assert not np.isfinite(p.best_cost)  # ...with the stale energy dropped
    first = p.ask()
    # ...warm: CSA solver 0 sits exactly at the incumbent's coordinates
    np.testing.assert_allclose(first[0], incumbent)
    assert drive(p, sphere) == 32  # full cold budget restored


def test_pipeline_reset_level2_is_cold():
    p = make_strategy("csa+nm", 2, num_opt=4, max_iter=8, seed=5)
    drive(p, sphere)
    p.reset(2)
    assert not np.isfinite(p.best_cost)
    assert p.stage_index == 0
    assert drive(p, sphere) == 32  # full cold budget again


def test_pipeline_enter_refinement_runs_final_stage_alone():
    csa = CSA(2, num_opt=4, max_iter=10, seed=2)
    nm = NelderMead(2, error=0.0, max_iter=1000, seed=2)
    p = Pipeline([csa, nm], (0.7, 0.3), budget=40)
    drive(p, sphere)
    assert p.is_end()
    assert p.enter_refinement()
    assert p.refining
    assert p.stage_index == 1
    assert not p.is_end()
    assert not np.isfinite(p.best_cost)  # energy re-proves post-drift
    # the refinement episode gets the final stage's nominal share: 0.3 * 40
    spent = drive(p, sphere)
    assert spent == 12
    # a later level-1 reset leaves refinement mode and restores the budget
    p.reset(1)
    assert not p.refining
    assert drive(p, sphere) == 40


def test_pipeline_seed_targets_current_stage():
    """A DB warm start seeds the *first* stage; after enter_refinement the
    same call seeds the refinement stage."""
    p = make_strategy("csa+nm", 1, num_opt=4, max_iter=8, seed=0)
    z0 = np.array([0.25])
    assert p.seed(z0, spread=0.1)
    first = p.ask()
    np.testing.assert_allclose(first[0], z0)  # CSA solver 0 == seed
    p.tell([sphere(z) for z in first])
    drive(p, sphere)
    p.enter_refinement()
    z1 = np.array([-0.5])
    assert p.seed(z1, spread=0.1)
    batch = p.ask()
    np.testing.assert_allclose(batch[0], z1)  # NM base vertex == seed


def test_pipeline_shrink_budget_scales_total():
    p = make_strategy("csa+nm", 1, num_opt=4, max_iter=10, seed=0)  # budget 40
    assert p.shrink_budget(0.5)
    assert drive(p, sphere) == 20


def test_pipeline_validates():
    with pytest.raises(ValueError):
        Pipeline([])
    with pytest.raises(ValueError):
        Pipeline([CSA(1, num_opt=2, max_iter=2), CSA(2, num_opt=2, max_iter=2)])
    with pytest.raises(ValueError):
        Pipeline([CSA(1, num_opt=2, max_iter=2)], (0.5, 0.5), budget=10)
    with pytest.raises(ValueError):  # fracs without a budget
        Pipeline([CSA(1, num_opt=2, max_iter=2)], (1.0,))


# ----------------------------------------------------------------- portfolio
def test_portfolio_interleaves_and_respects_budget():
    pf = Portfolio(
        [CSA(1, num_opt=4, max_iter=100, seed=0),
         NelderMead(1, error=0.0, max_iter=1000, seed=0)],
        budget=30,
    )
    assert drive(pf, sphere) == 30
    assert pf.is_end()


def test_portfolio_culls_separated_laggard_toward_leader():
    """The member whose best is statistically separated from the leader's is
    halved away; the survivor inherits the remaining budget."""
    good = NelderMead(1, error=0.0, max_iter=1000, seed=0)
    bad = RandomSearch(1, max_iter=1000, seed=0)
    pf = Portfolio([good, bad], budget=60, noise=NoiseEstimate(0.0, 0.0))
    drive(pf, sphere)
    assert len(pf.active) == 1  # exactly one arm survived successive halving
    winner = pf.active[0]
    bests = pf.member_bests
    assert bests[winner] == min(bests)  # ...and it is the leader
    assert pf.spent == 60  # the culled arm's allowance flowed to the leader
    assert pf.best_cost == bests[winner]


def test_portfolio_drip_feeds_oversized_member_rounds():
    """A member whose natural round exceeds one rung (a random sweep asks
    everything at once) is drip-fed across turns instead of monopolizing the
    budget — the other member still gets its interleaved share."""
    nm = NelderMead(1, error=0.0, max_iter=1000, seed=0)
    rs = RandomSearch(1, max_iter=1000, seed=0)
    pf = Portfolio([nm, rs], budget=20, noise=NoiseEstimate(1e9, 0.0), rung=2)
    drive(pf, sphere)
    assert pf.spent == 20
    # with a giant noise floor nothing is culled, so both arms consumed
    # interleaved rungs: NM must have advanced several tells, not just one
    assert nm.evaluations >= 6
    assert pf.active == [0, 1]


def test_portfolio_never_culls_inside_noise_floor():
    a = RandomSearch(1, max_iter=1000, seed=1)
    b = RandomSearch(1, max_iter=1000, seed=2)
    # a giant noise floor: no lead is ever statistically separated
    pf = Portfolio([a, b], budget=24, noise=NoiseEstimate(1e9, 0.0))
    drive(pf, sphere)
    assert pf.active == [0, 1]


def test_portfolio_set_noise_tightens_separation():
    a = RandomSearch(1, max_iter=1000, seed=1)
    b = RandomSearch(1, max_iter=1000, seed=2)
    pf = Portfolio([a, b], budget=24, noise=NoiseEstimate(1e9, 0.0))
    pf.set_noise(NoiseEstimate(0.0, 1e-6))
    costs = iter(range(100))
    drive(pf, lambda z: float(next(costs)))
    assert len(pf.active) == 1  # now the laggard separates and is culled


def test_portfolio_default_rung_caps_sweep_members():
    """A sweep-style member (grid: its 'round' is the whole sweep) must not
    swallow the shared budget in its first chunk — the default rung is
    capped at a fair share, so the other member still races and the cull
    checks fire."""
    pf = make_strategy("grid|csa", 2, num_opt=4, max_iter=20, seed=0)  # budget 80
    grid, csa = pf.members
    drive(pf, sphere)
    assert pf.spent == 80
    # both members actually consumed budget (pre-fix: grid took all 80)
    assert csa.iteration > 1  # CSA completed at least one told round
    assert grid.get_num_points() > pf._rung  # the cap engaged for the sweep


def test_portfolio_reset_reactivates_members():
    pf = make_strategy("csa|nm", 1, num_opt=4, max_iter=10, seed=0)
    drive(pf, sphere)
    assert len(pf.active) <= 2
    pf.reset(1)
    assert pf.active == [0, 1]
    assert not np.isfinite(pf.best_cost)
    assert drive(pf, sphere) == 40  # cold budget restored


def test_portfolio_validates():
    with pytest.raises(ValueError):
        Portfolio([CSA(1, num_opt=2, max_iter=2)])
    with pytest.raises(ValueError):
        Portfolio(
            [CSA(1, num_opt=2, max_iter=2), CSA(2, num_opt=2, max_iter=2)]
        )


# -------------------------------------------------------------------- parser
def test_make_strategy_bare_names_return_raw_optimizers():
    assert isinstance(make_strategy("csa", 2, num_opt=4, max_iter=5), CSA)
    assert isinstance(make_strategy("nm", 2), NelderMead)
    assert isinstance(make_strategy("random", 2), RandomSearch)
    assert isinstance(make_strategy("grid", 2), GridSearch)


def test_make_strategy_bare_csa_is_trajectory_identical_to_default():
    """strategy='csa' must be the default search bit-for-bit."""
    a = make_strategy("csa", 2, num_opt=3, max_iter=5, seed=7)
    b = CSA(2, num_opt=3, max_iter=5, seed=7)
    fa = fb = np.nan
    while not a.is_end():
        za, zb = a.run(fa), b.run(fb)
        np.testing.assert_array_equal(za, zb)
        fa = fb = sphere(za)
    assert b.is_end()


def test_make_strategy_budget_matches_default_csa():
    """Every spec consumes num_opt * max_iter tells — the Eq.1 product."""
    for spec in ("csa", "nm", "random", "csa+nm", "csa:0.6+nm:0.4", "csa|nm"):
        opt = make_strategy(spec, 2, num_opt=4, max_iter=6, seed=0)
        assert drive(opt, sphere) == 24, spec


def test_make_strategy_structures_and_spec_attr():
    p = make_strategy("csa+nm", 2, num_opt=4, max_iter=5)
    assert isinstance(p, Pipeline)
    assert [type(s) for s in p.stages] == [CSA, NelderMead]
    assert p.spec == "csa+nm"
    pf = make_strategy("csa|nm", 2, num_opt=4, max_iter=5)
    assert isinstance(pf, Portfolio)
    assert [type(m) for m in pf.members] == [CSA, NelderMead]
    assert pf.spec == "csa|nm"
    mixed = make_strategy("csa+nm|random", 2, num_opt=4, max_iter=5)
    assert isinstance(mixed, Portfolio)
    assert isinstance(mixed.members[0], Pipeline)
    assert isinstance(mixed.members[1], RandomSearch)


def test_make_strategy_default_split_is_exploration_heavy():
    p = make_strategy("csa+nm", 1, num_opt=4, max_iter=10)  # budget 40
    assert p._fracs == pytest.approx([0.7, 0.3])


def test_make_strategy_rejects_bad_specs():
    for bad in ("", "csa+", "|nm", "warp", "csa:1.4+nm", "csa:x+nm",
                "csa:0.9+nm:0.9+grid"):
        with pytest.raises(ValueError):
            make_strategy(bad, 2)


def test_strategy_label_round_trips():
    assert strategy_label(make_strategy("csa+nm", 2)) == "csa+nm"
    assert strategy_label(make_strategy("csa|nm", 2)) == "csa|nm"
    assert strategy_label(CSA(1, num_opt=2, max_iter=2)) == "csa"
    assert strategy_label(NelderMead(2)) == "nm"
    lbl = strategy_label(
        Pipeline(
            [CSA(1, num_opt=2, max_iter=4), NelderMead(1, max_iter=8)],
            (0.75, 0.25), budget=16,
        )
    )
    assert lbl == "csa:0.75+nm:0.25"
    # a non-default split is never elided: the recorded provenance must
    # re-parse to the SAME budget shares that produced the record
    uniform = Pipeline(
        [CSA(1, num_opt=2, max_iter=4), NelderMead(1, max_iter=8)],
        budget=16,  # Pipeline's own default split is uniform, not 0.7/0.3
    )
    assert strategy_label(uniform) == "csa:0.5+nm:0.5"
    rebuilt = make_strategy(strategy_label(uniform), 1, budget=16)
    assert rebuilt._fracs == pytest.approx([0.5, 0.5])


# --------------------------------------------------------- Autotuning wiring
def test_autotuning_strategy_spec_and_exclusivity():
    at = Autotuning(-10, 10, ignore=0, dim=2, strategy="csa+nm",
                    num_opt=4, max_iter=20, seed=2)
    at.entire_exec(lambda a, b: float((a - 4) ** 2 + (b + 6) ** 2))
    assert at.best_point == {"p0": 4, "p1": -6}
    assert at.strategy == "csa+nm"
    assert at.num_measurements == 80  # same Eq.1 budget as the default CSA
    with pytest.raises(ValueError):
        Autotuning(dim=1, strategy="csa", optimizer=CSA(1, num_opt=2, max_iter=2))


@pytest.mark.parametrize("ignore", [0, 1, 2])
def test_pipeline_keeps_eq1_measurement_count_with_ignore(ignore):
    """A strategy-built pipeline spends the default CSA's Eq.1 budget,
    max_iter * (ignore + 1) * num_opt, through the Autotuning driver."""
    at = Autotuning(0, 63, ignore=ignore, dim=1, strategy="csa+nm", num_opt=4, max_iter=5)
    at.entire_exec(lambda p: (p - 31) ** 2)
    assert at.num_measurements == 5 * (ignore + 1) * 4


def test_autotuning_single_optimizer_trajectory_pinned():
    """Regression pin: optimizer=CSA construction is bit-for-bit identical to
    the pre-strategy-layer driver (visited points and costs hard-coded)."""
    at = Autotuning(1, 64, ignore=0, optimizer=CSA(2, num_opt=3, max_iter=5, seed=7),
                    dim=2)
    at.entire_exec(lambda a, b: float((a - 37) ** 2 + (b - 5) ** 2))
    pin = [
        (40, 58, 2818.0), (50, 15, 269.0), (20, 56, 2890.0), (33, 20, 241.0),
        (8, 14, 922.0), (9, 43, 2228.0), (31, 20, 261.0), (52, 2, 234.0),
        (22, 47, 1989.0), (24, 5, 169.0), (55, 8, 333.0), (16, 47, 2205.0),
        (26, 6, 122.0), (52, 59, 3141.0), (35, 38, 1093.0),
    ]
    assert [(p["p0"], p["p1"], c) for p, c in at.history] == pin
    assert at.best_point == {"p0": 26, "p1": 6}
    # ... and the batch driver walks the identical trajectory
    at2 = Autotuning(1, 64, ignore=0,
                     optimizer=CSA(2, num_opt=3, max_iter=5, seed=7), dim=2)
    at2.entire_exec_batch(
        lambda pts: [float((p["p0"] - 37) ** 2 + (p["p1"] - 5) ** 2) for p in pts]
    )
    assert [(p["p0"], p["p1"], c) for p, c in at2.history] == pin


def test_pipeline_not_worse_than_csa_on_shootout_models():
    """Acceptance: Pipeline([CSA, NM]) with a shared budget finds a best
    <= pure CSA's on the deterministic cost models, at the same total tell
    count."""
    budget = 120
    for fname, fn in COST_MODELS.items():
        pipe_bests, csa_bests = [], []
        for seed in range(3):
            pipe = make_strategy("csa+nm", 2, num_opt=4, max_iter=budget // 4,
                                 seed=seed)
            csa = make_strategy("csa", 2, num_opt=4, max_iter=budget // 4,
                                seed=seed)
            assert drive(pipe, fn) == budget
            assert drive(csa, fn) == budget
            pipe_bests.append(pipe.best_cost)
            csa_bests.append(csa.best_cost)
        assert np.median(pipe_bests) <= np.median(csa_bests), fname


def test_autotuning_warm_start_seeds_first_stage_only(tmp_path):
    """A DB near-miss seeds the pipeline's *first* stage around the stored
    point (and shrinks the total budget); the NM stage still gets its seed
    from the CSA handoff, not from the DB."""
    db = TuningDB(str(tmp_path / "db.json"))
    sp = SearchSpace([IntDim("p", 1, 64)])
    stored = make_key("unit", args=(np.zeros((64, 64), np.float32),), space=sp)
    db.put(TuningRecord(key=stored, point={"p": 48}, cost=1.0, evals=8))
    near = make_key("unit", args=(np.zeros((128, 128), np.float32),), space=sp)
    at = Autotuning(space=sp, ignore=0, strategy="csa+nm",
                    num_opt=4, max_iter=10, seed=0, db=db, key=near)
    assert at.warm_started
    assert at.point == {"p": 48}  # first candidate: CSA solver 0 == seed
    pipe = at.optimizer
    assert isinstance(pipe, Pipeline)
    at.entire_exec_batch(lambda pts: [float((p["p"] - 40) ** 2) for p in pts])
    assert pipe.spent <= 20  # budget halved (cold: 40)
    assert abs(at.best_point["p"] - 40) <= 1  # half-budget refinement lands


def test_tuned_step_accepts_strategy():
    space = SearchSpace([IntDim("n", 1, 6)])
    calls = []

    def factory(n):
        calls.append(n)
        return lambda: n

    ts = TunedStep(factory, space, ignore=0, strategy="csa+nm",
                   num_opt=3, max_iter=4, seed=1)
    assert isinstance(ts.at.optimizer, Pipeline)
    for _ in range(40):
        if ts.finished:
            break
        ts()
    assert ts.finished


# --------------------------------------------------------------- provenance
def test_record_strategy_round_trips_and_old_records_load_none(tmp_path):
    sp = SearchSpace([IntDim("p", 1, 32)])
    key = make_key("unit", space=sp)
    db = TuningDB(str(tmp_path / "db.json"))
    at = Autotuning(space=sp, ignore=0, strategy="csa+nm", num_opt=3,
                    max_iter=4, seed=0, db=db, key=key)
    at.entire_exec(lambda p: float((p - 9) ** 2))
    rec = db.get(key)
    assert rec is not None and rec.strategy == "csa+nm"
    # JSON round trip preserves the spec
    rec2 = TuningRecord.from_json(json.loads(json.dumps(rec.to_json())))
    assert rec2.strategy == "csa+nm"
    # a pre-strategy record (no field at all) loads as None...
    blob = rec.to_json()
    del blob["strategy"]
    old = TuningRecord.from_json(blob)
    assert old.strategy is None
    # ...and still replays as an exact hit
    db2 = TuningDB(str(tmp_path / "db2.json"))
    db2.put(old)
    replay = Autotuning(space=sp, ignore=0, db=db2, key=key)
    assert replay.finished
    assert replay.best_point == old.point
    assert replay.num_measurements == 0


def test_default_optimizer_records_csa_strategy(tmp_path):
    sp = SearchSpace([IntDim("p", 1, 16)])
    key = make_key("unit2", space=sp)
    db = TuningDB(str(tmp_path / "db.json"))
    at = Autotuning(space=sp, ignore=0, num_opt=3, max_iter=3, seed=0,
                    db=db, key=key)
    at.entire_exec(lambda p: float(p))
    rec = db.get(key)
    assert rec is not None and rec.strategy == "csa"


def test_pretune_list_shows_strategy_column(tmp_path, capsys):
    """pretune --list prints the stored record's strategy on exact hits."""
    pytest.importorskip("jax")
    from repro.tuning import pretune

    db_path = str(tmp_path / "db.json")
    rc = pretune.main(
        ["--db", db_path, "--smoke", "--only", "lru_scan/*",
         "--strategy", "csa+nm", "--max-iter", "2", "--jobs", "1"]
    )
    assert rc == 0
    out = capsys.readouterr().out
    assert "strategy=csa+nm" in out
    rc = pretune.main(["--db", db_path, "--smoke", "--only", "lru_scan/*", "--list"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "HIT" in out and "strategy=csa+nm" in out
