"""Launch-level tuning tests (ISSUE 10): declarative constraints in the
driver, launch search spaces, tuned-launch DB round-trips, chunked psum."""
import json

import pytest

from repro.core import Autotuning, Constraint, IntDim, SearchSpace
from repro.tuning.records import space_fingerprint

from helpers import run_py


def _toy_space(constrained: bool = True) -> SearchSpace:
    cons = (
        [Constraint("prod-4", lambda p: p["a"] * p["b"] == 4,
                    describe="a*b must equal 4")]
        if constrained else []
    )
    return SearchSpace([IntDim("a", 1, 4), IntDim("b", 1, 4)],
                       constraints=cons)


# ------------------------------------------------------- constraint basics
def test_constraint_check_reports_first_violation():
    sp = SearchSpace(
        [IntDim("a", 1, 4)],
        constraints=[
            Constraint("even", lambda p: p["a"] % 2 == 0),
            Constraint("big", lambda p: p["a"] >= 3),
        ],
    )
    assert sp.check({"a": 4}) is None
    assert sp.check({"a": 3}) == "even"
    assert sp.check({"a": 2}) == "big"
    assert sp.check({"a": 1}) == "even"  # first violated name wins


def test_constraint_predicate_exception_counts_as_violation():
    sp = SearchSpace(
        [IntDim("a", 0, 3)],
        constraints=[Constraint("div", lambda p: 6 % p["a"] == 0)],
    )
    assert sp.check({"a": 0}) == "div"  # ZeroDivisionError -> invalid


def test_size_and_constrained_size():
    sp = _toy_space()
    assert sp.size() == 16
    # (1,4), (2,2), (4,1) are the only products equal to 4
    assert sp.constrained_size() == 3
    assert _toy_space(constrained=False).constrained_size() == 16


def test_fingerprint_stable_for_unconstrained_spaces():
    """Adding the constraints feature must not move existing kernel
    fingerprints; attaching constraints must."""
    dims = lambda: [IntDim("a", 1, 4), IntDim("b", 1, 4)]  # noqa: E731
    plain = space_fingerprint(SearchSpace(dims()))
    assert plain == space_fingerprint(SearchSpace(dims(), constraints=[]))
    assert plain != space_fingerprint(_toy_space())


# --------------------------------------------------- driver-level pruning
def _grid(sp: SearchSpace) -> Autotuning:
    """Exhaustive deterministic scan: visits all 16 grid points, so exactly
    the 13 invalid ones get pruned and the true optimum must surface."""
    from repro.core import GridSearch

    return Autotuning(space=sp, search=GridSearch(2, points_per_dim=4),
                      cache=True)


def test_sequential_search_never_presents_invalid_points():
    sp = _toy_space()
    at = _grid(sp)
    presented = []
    p = at.start()
    while not at.finished:
        assert sp.check(p) is None, f"driver presented invalid point {p}"
        presented.append(dict(p))
        p = at.exec(float((p["a"] - 2) ** 2 + (p["b"] - 2) ** 2))
    assert presented, "search presented no points at all"
    assert at.best_point == {"a": 2, "b": 2}
    assert at.skip_reasons.get("constraint", 0) == 13  # 16 grid - 3 valid
    assert sum(at.constraint_violations.values()) == at.skip_reasons["constraint"]
    # constraint prunes are bookkeeping, not failures
    assert at.num_crashed == 0


def test_batch_search_prunes_before_measurement():
    sp = _toy_space()
    at = _grid(sp)

    def measure(points):
        for p in points:
            assert sp.check(p) is None, f"measure_batch saw invalid {p}"
        return [float((p["a"] - 2) ** 2 + (p["b"] - 2) ** 2) for p in points]

    at.entire_exec_batch(measure)
    assert at.best_point == {"a": 2, "b": 2}
    assert at.skip_reasons.get("constraint", 0) == 13
    assert "prod-4" in at.constraint_violations


def test_pruned_points_revisitable_after_reset():
    sp = _toy_space()
    at = Autotuning(space=sp, num_opt=3, max_iter=4, seed=0, cache=True)
    at.entire_exec_batch(lambda pts: [1.0] * len(pts))
    n0 = sum(at.constraint_violations.values())
    assert n0 > 0
    at.reset(1)  # level>=1 clears the pruned-key memory
    at.entire_exec_batch(lambda pts: [1.0] * len(pts))
    assert sum(at.constraint_violations.values()) > n0


def test_constraint_events_balance(tmp_path):
    """asked == committed + culled + pruned + skipped + quarantined must
    keep holding when the driver charges constraint prunes."""
    from repro.obs import completeness
    from repro.obs.events import EventSink, set_sink

    sp = _toy_space()
    epath = str(tmp_path / "events.jsonl")
    sink = EventSink(epath)
    set_sink(sink)
    try:
        at = Autotuning(space=sp, num_opt=3, max_iter=5, seed=0, cache=True)
        at.entire_exec_batch(
            lambda pts: [float(p["a"] + p["b"]) for p in pts]
        )
    finally:
        set_sink(None)
        sink.close()
    acc = completeness(epath)
    name = at.ctx_name()
    assert acc[name]["balanced"], acc[name]
    assert acc[name]["skipped"] >= at.skip_reasons.get("constraint", 0) > 0


# --------------------------------------------------------- launch spaces
ZOO = ["qwen2_7b", "recurrentgemma_2b", "moonshot_v1_16b_a3b"]


def test_launch_space_default_point_is_valid():
    from repro import configs
    from repro.launch.spaces import default_launch_point, launch_space

    for arch in ZOO:
        cfg = configs.get(arch)
        shape = configs.SHAPES["train_4k"]
        sp = launch_space(cfg, shape, 8)
        pt = default_launch_point(cfg, shape, 8, sp)
        assert sp.check(pt) is None, (arch, pt)
        assert pt["dp"] * pt["tp"] == 8


def test_launch_space_constraints_collapse_raw_space():
    from repro import configs
    from repro.launch.spaces import launch_space

    cfg = configs.get("qwen2_7b")
    sp = launch_space(cfg, configs.SHAPES["train_4k"], 8)
    raw, feas = sp.size(), sp.constrained_size()
    assert raw is not None and feas is not None
    assert 0 < feas < raw
    # every grid survivor factorizes the device count
    for pt in sp.grid_points():
        if sp.check(pt) is None:
            assert pt["dp"] * pt["tp"] == 8


def test_launch_cost_model_deterministic_and_monotone():
    from repro import configs
    from repro.launch.spaces import default_launch_point, launch_cost_model, launch_space

    cfg = configs.get("qwen2_7b")
    shape = configs.SHAPES["train_4k"]
    cost = launch_cost_model(cfg, shape, 8)
    sp = launch_space(cfg, shape, 8)
    pt = default_launch_point(cfg, shape, 8, sp)
    assert cost(pt) == cost(dict(pt))  # pure function of the point
    # remat="full" recomputes more than "none", all else equal
    lean, fat = dict(pt, remat="full"), dict(pt, remat="none")
    assert cost(lean) != cost(fat)


def test_tune_launch_commits_and_replays(tmp_path):
    from repro.launch.spaces import tune_launch
    from repro.tuning import TuningDB

    db = TuningDB(str(tmp_path / "launch.json"))
    s1: dict = {}
    rec = tune_launch("qwen2_7b", "train_4k", 8, db=db, mode="model",
                      max_iter=3, warm_start=False, stats=s1)
    assert rec is not None and rec.source == "pretune"
    assert rec.cost <= s1["default_cost"] * (1 + 1e-9)
    assert rec.key.shapes() is None  # no array args: context lives in extra
    assert json.loads(rec.key.extra)["shape"] == "train_4k"
    db.save()

    s2: dict = {}
    rec2 = tune_launch("qwen2_7b", "train_4k", 8, db=db, mode="model",
                       max_iter=3, stats=s2)
    assert s2["replayed"] and s2["measured"] == 0
    assert rec2.point == rec.point and rec2.cost == rec.cost


def test_launch_keys_roundtrip_db_cli(tmp_path, capsys):
    """Satellite 6: knobs-only launch keys survive db merge/diff/list."""
    from repro.launch.spaces import tune_launch
    from repro.tune import main as tune_main
    from repro.tuning import TuningDB

    a, b = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    dba, dbb = TuningDB(a), TuningDB(b)
    tune_launch("qwen2_7b", "train_4k", 8, db=dba, mode="model",
                max_iter=2, warm_start=False)
    tune_launch("recurrentgemma_2b", "train_4k", 8, db=dbb, mode="model",
                max_iter=2, warm_start=False)
    dba.save(), dbb.save()

    merged = str(tmp_path / "m.json")
    assert tune_main(["db", "merge", "--out", merged, a, b]) == 0
    assert len(TuningDB(merged)) == 2
    # a merged db agrees with each source on the records it contributed
    assert tune_main(["db", "diff", a, a]) == 0
    rc_diff = tune_main(["db", "diff", merged, a])
    assert rc_diff == 1  # b's record is missing from a -> reported, not crash

    assert tune_main(["db", "list", "--db", merged]) == 0
    out = capsys.readouterr().out
    assert "launch/qwen2_7b" in out and "launch/recurrentgemma_2b" in out
    assert "shape=train_4k" in out and "None" not in out


@pytest.mark.parametrize("arch,shape_name", [
    ("qwen2_7b", "train_4k"), ("recurrentgemma_2b", "train_4k"),
    ("moonshot_v1_16b_a3b", "train_4k"),
])
def test_tune_launch_smoke_case_beats_default_and_scores_only_valid_points(
        tmp_path, arch, shape_name):
    """Each smoke launch case commits a launch no slower than the untuned
    default, its constraints prune part of the raw space, and the event
    stream shows only valid points scored, only invalid points charged as
    constraint prunes, and balanced candidate accounting."""
    from repro import configs
    from repro.launch.spaces import launch_cases, launch_space, tune_launch
    from repro.obs import completeness, read_events
    from repro.obs.events import EventSink, set_sink
    from repro.tuning import TuningDB

    assert (arch, shape_name) in launch_cases(smoke=True)
    space = launch_space(configs.get(arch), configs.SHAPES[shape_name], 8)
    epath = str(tmp_path / "events.jsonl")
    sink = EventSink(epath)
    set_sink(sink)
    stats: dict = {}
    try:
        rec = tune_launch(arch, shape_name, 8, db=TuningDB(str(tmp_path / "l.json")),
                          mode="model", num_opt=3, max_iter=4, seed=0,
                          warm_start=False, stats=stats)
    finally:
        set_sink(None)
        sink.close()
    assert rec is not None
    assert rec.cost <= stats["default_cost"] * (1 + 1e-9)
    assert 0.0 < 1.0 - stats["constrained_size"] / stats["raw_size"] < 1.0
    events = [e for e in read_events(epath) if e.get("name") == f"launch/{arch}"]
    committed = [e for e in events if e.get("type") == "candidate_committed"]
    pruned = [e for e in events if e.get("type") == "candidate_skipped"
              and e.get("reason") == "constraint"]
    assert len(committed) == stats["measured"] and len(pruned) == stats["pruned"] > 0
    assert all(space.check(e["point"]) is None for e in committed)
    assert all(space.check(e["point"]) is not None for e in pruned)
    assert completeness(events)[f"launch/{arch}"]["balanced"]


# ----------------------------------------------------- chunked collectives
@pytest.mark.multidevice
def test_chunked_psum_matches_dense():
    code = """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.parallel.collectives import chunked_psum

mesh = make_mesh((4,), ("data",))
g = jax.random.normal(jax.random.PRNGKey(0), (4, 37, 5))  # non-divisible size

def red(chunk_bytes):
    def f(gl):
        return chunked_psum(gl[0], "data", chunk_bytes)
    return jax.shard_map(f, mesh=mesh, in_specs=(P("data", None, None),),
                     out_specs=P(), check_vma=False)(g)

exact = jnp.sum(g, axis=0)
for cb in (64, 256, 10**9):  # many chunks, a few, and one monolithic psum
    out = red(cb)
    assert out.shape == exact.shape
    assert jnp.allclose(out, exact, atol=1e-5), cb
try:
    red(0)
    raise SystemExit("chunk_bytes=0 must raise")
except ValueError:
    pass
print("OK")
"""
    assert "OK" in run_py(code, devices=4)


# --------------------------------------------------------- dryrun hygiene
@pytest.mark.multidevice
def test_dryrun_preserves_existing_xla_flags():
    """Satellite 1: importing launch.dryrun must keep caller XLA flags and
    honor REPRO_DRYRUN_DEVICES instead of clobbering the whole variable."""
    code = """
import os
import repro.launch.dryrun  # noqa: F401  (import applies the device-count flag)
flags = os.environ["XLA_FLAGS"].split()
assert "--xla_cpu_enable_fast_math=false" in flags, flags
assert "--xla_force_host_platform_device_count=4" in flags, flags
assert sum(f.startswith("--xla_force_host_platform_device_count") for f in flags) == 1
import jax
assert jax.device_count() == 4
print("OK")
"""
    out = run_py(
        code,
        devices=2,  # helpers sets ...device_count=2; dryrun must replace it
        env_extra={
            "XLA_FLAGS": "--xla_cpu_enable_fast_math=false "
                         "--xla_force_host_platform_device_count=2",
            "REPRO_DRYRUN_DEVICES": "4",
        },
    )
    assert "OK" in out
