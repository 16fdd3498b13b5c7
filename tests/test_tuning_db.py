"""Persistent tuning DB: round-trip, cross-process fingerprint stability,
corrupt-file recovery, exact-hit replay, and near-miss warm-start budgets."""
import json
import os

import numpy as np
import pytest

from repro.core import CSA, Autotuning, IntDim, LogIntDim, NelderMead, SearchSpace
from repro.tuning import (
    SCHEMA_VERSION,
    TuningDB,
    TuningKey,
    TuningRecord,
    make_key,
    space_fingerprint,
)

from helpers import run_py


def _space():
    return SearchSpace([IntDim("p", 1, 32)])


def _key(shape=(64, 64), name="unit", space=None):
    return make_key(name, args=(np.zeros(shape, np.float32),), space=space or _space())


# ------------------------------------------------------------- persistence
def test_round_trip_persistence(tmp_path):
    path = str(tmp_path / "db.json")
    db = TuningDB(path)
    key = _key()
    rec = TuningRecord(key=key, point={"p": 9}, cost=0.125, evals=40, source="pretune")
    db.put(rec)

    db2 = TuningDB(path)  # fresh handle = fresh process's view
    got = db2.get(key)
    assert got is not None
    assert got.point == {"p": 9}
    assert got.cost == 0.125
    assert got.evals == 40
    assert got.source == "pretune"
    assert got.key == key

    blob = json.load(open(path))
    assert blob["schema"] == SCHEMA_VERSION


def test_atomic_save_leaves_no_temp_files(tmp_path):
    path = str(tmp_path / "db.json")
    db = TuningDB(path)
    for i in range(5):
        db.put(TuningRecord(key=_key(shape=(64, 64 + i)), point={"p": i + 1}, cost=float(i)))
    leftovers = [f for f in os.listdir(tmp_path) if f != "db.json"]
    assert leftovers == []
    assert len(TuningDB(path)) == 5


def test_corrupted_file_recovery(tmp_path):
    path = str(tmp_path / "db.json")
    db = TuningDB(path)
    db.put(TuningRecord(key=_key(), point={"p": 9}, cost=1.0))
    with open(path, "w") as f:
        f.write('{"schema": 1, "records": {truncated garbage')

    db2 = TuningDB(path)  # must not raise
    assert len(db2) == 0
    assert os.path.exists(path + ".corrupt")  # quarantined, not destroyed
    # and the DB is usable again
    db2.put(TuningRecord(key=_key(), point={"p": 5}, cost=2.0))
    assert TuningDB(path).get(_key()).point == {"p": 5}


def test_newer_schema_is_ignored_not_destroyed(tmp_path):
    path = str(tmp_path / "db.json")
    with open(path, "w") as f:
        json.dump({"schema": SCHEMA_VERSION + 1, "records": {"k": {}}}, f)
    db = TuningDB(path)
    assert len(db) == 0
    assert json.load(open(path))["schema"] == SCHEMA_VERSION + 1  # untouched


# ------------------------------------------------------------ fingerprints
def test_key_distinguishes_contexts():
    sp = _space()
    base = _key(space=sp)
    assert _key(space=sp) == base  # deterministic
    assert _key(shape=(64, 128), space=sp) != base  # shapes keyed
    assert _key(name="other", space=sp) != base  # name keyed
    sp2 = SearchSpace([IntDim("p", 1, 64)])  # bounds changed -> new space
    assert _key(space=sp2) != base
    assert make_key("unit", space=sp, extra={"b": 8}) != make_key(
        "unit", space=sp, extra={"b": 16}
    )


def test_space_fingerprint_ignores_nothing_structural():
    a = SearchSpace([LogIntDim("bm", 32, 256), IntDim("n", 1, 4)])
    b = SearchSpace([LogIntDim("bm", 32, 256), IntDim("n", 1, 4)])
    c = SearchSpace([LogIntDim("bm", 32, 512), IntDim("n", 1, 4)])
    assert space_fingerprint(a) == space_fingerprint(b)
    assert space_fingerprint(a) != space_fingerprint(c)


def test_fingerprint_stable_across_processes():
    """The on-disk dict key must be identical when computed in a different
    interpreter (no Python hash() anywhere in the pipeline)."""
    code = (
        "import numpy as np\n"
        "from repro.core import SearchSpace, IntDim\n"
        "from repro.tuning import make_key\n"
        "k = make_key('unit', args=(np.zeros((64, 64), np.float32),),\n"
        "             space=SearchSpace([IntDim('p', 1, 32)]))\n"
        "print(k.encode())\n"
    )
    remote = run_py(code, devices=1).strip().splitlines()[-1]
    local = _key().encode()
    # backend/device fields may legitimately differ between the processes if
    # XLA flags differ; everything else must match exactly
    r_parts, l_parts = remote.split("|"), local.split("|")
    assert r_parts[:4] == l_parts[:4]
    assert r_parts == l_parts  # same host, same backend -> full equality


def test_record_json_round_trip():
    rec = TuningRecord(key=_key(), point={"p": 7}, cost=3.5, evals=12, source="online")
    back = TuningRecord.from_json(json.loads(json.dumps(rec.to_json())))
    assert back.key == rec.key
    assert back.point == rec.point
    assert back.cost == rec.cost


# -------------------------------------------------------- warm-start paths
def _count_cost(target=9):
    calls = {"n": 0}

    def cost(p):
        calls["n"] += 1
        return (p - target) ** 2

    return calls, cost


def test_exact_hit_zero_measurements(tmp_path):
    """Tuning the same key twice: the second run replays the stored best with
    zero cost evaluations (acceptance criterion)."""
    path = str(tmp_path / "db.json")
    sp = _space()
    key = _key(space=sp)

    calls, cost = _count_cost()
    at = Autotuning(space=sp, optimizer=CSA(1, num_opt=4, max_iter=10, seed=0),
                    db=TuningDB(path), key=key)
    at.entire_exec(cost)
    assert calls["n"] > 0
    assert at.best_point == {"p": 9}

    calls2, cost2 = _count_cost()
    at2 = Autotuning(space=sp, optimizer=CSA(1, num_opt=4, max_iter=10, seed=0),
                     db=TuningDB(path), key=key)  # fresh handle = second process
    assert at2.finished
    assert at2.warm_started
    at2.entire_exec(cost2)  # no-op: already finished
    assert calls2["n"] == 0
    assert at2.point == {"p": 9}
    assert at2.best_point == {"p": 9}
    assert at2.num_measurements == 0


def test_near_miss_halves_evaluations(tmp_path):
    """A different-shape key seeded from a stored neighbor must converge in
    <= 50% of the cold-start cost evaluations (acceptance criterion)."""
    path = str(tmp_path / "db.json")
    sp = _space()

    def tuned_run(db, key):
        calls, cost = _count_cost()
        at = Autotuning(space=sp, optimizer=CSA(1, num_opt=4, max_iter=10, seed=0),
                        db=db, key=key, cache=False)
        at.entire_exec(cost)
        return calls["n"], at

    cold_key = _key(shape=(64, 64), space=sp)
    cold_evals, cold_at = tuned_run(TuningDB(path), cold_key)
    assert cold_at.best_point == {"p": 9}

    near_key = _key(shape=(128, 128), space=sp)  # same computation, new shape
    warm_evals, warm_at = tuned_run(TuningDB(path), near_key)
    assert warm_at.warm_started
    assert warm_evals <= cold_evals // 2
    assert warm_at.best_point == {"p": 9}  # still converges


def test_near_miss_seeds_nelder_mead(tmp_path):
    path = str(tmp_path / "db.json")
    sp = _space()
    db = TuningDB(path)
    db.put(TuningRecord(key=_key(shape=(64, 64), space=sp), point={"p": 9}, cost=0.0))

    calls, cost = _count_cost()
    at = Autotuning(space=sp, optimizer=NelderMead(1, error=0.0, max_iter=40, seed=0),
                    db=db, key=_key(shape=(32, 32), space=sp))
    assert at.warm_started
    at.entire_exec(cost)
    assert calls["n"] <= 20  # budget halved
    assert at.best_point == {"p": 9}


def test_reset_reenters_tuning_after_db_hit(tmp_path):
    """Watchdog reset semantics survive DB replay: a reset after an exact hit
    re-enters real tuning and re-commits the fresh result."""
    path = str(tmp_path / "db.json")
    sp = _space()
    key = _key(space=sp)
    db = TuningDB(path)
    db.put(TuningRecord(key=key, point={"p": 3}, cost=1.0))

    at = Autotuning(space=sp, optimizer=CSA(1, num_opt=4, max_iter=10, seed=0),
                    db=db, key=key)
    assert at.finished and at.point == {"p": 3}
    at.reset(2)  # environment drifted
    assert not at.finished
    calls, cost = _count_cost(target=20)
    at.entire_exec(cost)
    assert calls["n"] > 0
    assert abs(at.best_point["p"] - 20) <= 1  # moved off the stale optimum
    assert TuningDB(path).get(key).point == at.best_point  # re-committed


def test_commit_happens_automatically_on_finish(tmp_path):
    path = str(tmp_path / "db.json")
    sp = _space()
    key = _key(space=sp)
    at = Autotuning(space=sp, optimizer=CSA(1, num_opt=3, max_iter=4, seed=1),
                    db=TuningDB(path), key=key)
    _, cost = _count_cost()
    at.entire_exec(cost)
    rec = TuningDB(path).get(key)
    assert rec is not None
    assert rec.point == at.best_point
    assert rec.evals == at.num_evals


# -------------------------------------------------- kernel dispatch layer
def test_autotuned_kernel_exact_hit_and_correctness(tmp_path):
    import jax

    from repro.kernels import autotuned, ref
    from repro.kernels.autotuned import get_spec, tune_call

    path = str(tmp_path / "k.json")
    db = TuningDB(path)
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 64))
    b = jax.random.normal(jax.random.PRNGKey(1), (64, 64))

    # cold: registered defaults, still correct
    o = autotuned("matmul", a, b, db=db, interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref.matmul_ref(a, b)), atol=1e-4)
    assert len(db) == 0

    rec = tune_call("matmul", a, b, db=db, interpret=True, max_iter=2)
    assert set(rec.point) == {"bm", "bn", "bk"}
    assert len(TuningDB(path)) == 1  # persisted

    # warm: dispatch uses the stored point (exact fingerprint hit; interpret
    # mode is part of the fingerprint — interpreter timings never leak into
    # compiled dispatch)
    spec = get_spec("matmul")
    key = make_key("matmul", args=(a, b), space=spec.space(a, b),
                   extra={"interpret": True})
    assert TuningDB(path).get(key) is not None
    assert TuningDB(path).get(
        make_key("matmul", args=(a, b), space=spec.space(a, b),
                 extra={"interpret": False})) is None
    o = autotuned("matmul", a, b, db=TuningDB(path), interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref.matmul_ref(a, b)), atol=1e-4)


def test_autotuned_neighbor_point_clamped_into_smaller_space(tmp_path):
    import jax

    from repro.kernels import autotuned, ref
    from repro.kernels.autotuned import get_spec

    db = TuningDB(str(tmp_path / "k.json"))
    big_a = jax.random.normal(jax.random.PRNGKey(0), (256, 256))
    big_b = jax.random.normal(jax.random.PRNGKey(1), (256, 256))
    spec = get_spec("matmul")
    db.put(
        TuningRecord(
            key=make_key("matmul", args=(big_a, big_b), space=spec.space(big_a, big_b)),
            point={"bm": 256, "bn": 256, "bk": 256},
            cost=0.001,
        )
    )
    # smaller problem: neighbor's 256-tiles must clamp to the 64-space
    a = jax.random.normal(jax.random.PRNGKey(2), (64, 64))
    b = jax.random.normal(jax.random.PRNGKey(3), (64, 64))
    o = autotuned("matmul", a, b, db=db, interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref.matmul_ref(a, b)), atol=1e-4)


def test_committed_snapshot_replays(tmp_path):
    """The repo's tuned/cpu.json snapshot must load under the current schema
    and yield an exact fingerprint hit for a pretune grid entry — this guards
    fingerprint stability across code changes."""
    import jax

    from repro.kernels.autotuned import get_spec

    snap = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "tuned", "cpu.json")
    if not os.path.exists(snap):
        pytest.skip("no committed snapshot")
    db = TuningDB(snap)
    assert len(db) > 0
    # cpu-backend records only apply on a cpu host
    if db.records()[0].key.backend != "cpu" or jax.default_backend() != "cpu":
        pytest.skip("snapshot is for a different backend")
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 64))
    b = jax.random.normal(jax.random.PRNGKey(1), (64, 64))
    spec = get_spec("matmul")
    key = make_key("matmul", args=(a, b), space=spec.space(a, b),
                   extra={"interpret": True})
    rec = db.get(key)
    assert rec is not None, "fingerprint drifted: snapshot key no longer matches"
    assert set(rec.point) == {"bm", "bn", "bk"}


def test_tuned_step_warm_start(tmp_path):
    """TunedStep with a DB: second construction replays without tuning."""
    import jax.numpy as jnp

    from repro.core import TunedStep

    path = str(tmp_path / "step.json")
    sp = SearchSpace([IntDim("n", 1, 4)])

    def factory(n):
        def step(x):
            for _ in range(n):
                x = x + 1.0
            return x

        return step

    ts = TunedStep(factory, sp, ignore=0, num_opt=3, max_iter=3, seed=0,
                   db=TuningDB(path), name="unit_step", key_extra={"b": 8})
    ts.tune(jnp.zeros((4,)))
    assert ts.finished

    ts2 = TunedStep(factory, sp, ignore=0, num_opt=3, max_iter=3, seed=0,
                    db=TuningDB(path), name="unit_step", key_extra={"b": 8})
    assert ts2.finished  # replayed before any step ran
    assert ts2.best_knobs == ts.best_knobs
    out = ts2(jnp.zeros((4,)))  # runs the stored-best step directly
    assert out.shape == (4,)


def test_default_device_raises_when_backend_fails(monkeypatch):
    """No silent ('none', 'unknown') key: a backend that fails to start is an error."""
    import jax

    from repro.tuning import default_device

    def broken():
        raise RuntimeError("backend failed to start")

    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="backend failed"):
        default_device()


def test_roofline_peaks_only_for_known_device_kinds():
    """Peaks come from the table keyed by device_kind; any other kind gets no
    roofline bound, never v5e's."""
    import jax
    import jax.numpy as jnp

    from repro.core import PEAKS, TPU_V5E, peaks_for
    from repro.kernels.autotuned import _roofline_bound

    assert peaks_for("TPU v5 lite") is TPU_V5E and PEAKS["TPU v5 lite"].peak_flops == 197e12
    assert peaks_for("cpu") is None
    ex = jax.jit(lambda x: x @ x).lower(jnp.ones((64, 64))).compile()
    assert _roofline_bound(ex) is None  # the CPU device has no published peaks


def test_matmul_space_offers_only_lane_legal_tiles():
    """bn/bk are the last dims of TPU blocks: multiples of 128, or the full dim."""
    import jax.numpy as jnp

    from repro.kernels.autotuned import get_spec

    spec = get_spec("matmul")
    for m, k, n in [(2048, 3584, 18944), (64, 64, 64), (96, 192, 384)]:
        a, b = jnp.zeros((m, k)), jnp.zeros((k, n))
        space = spec.space(a, b)
        dims = {d.name: d for d in space.dims}
        defaults = spec.defaults(a, b)
        for name, full in (("bn", n), ("bk", k)):
            for z in (-1.0, 0.0, 1.0):
                v = dims[name].decode(z)
                assert v == full or (v % 128 == 0 and full % v == 0), (name, full, v)
            assert defaults[name] == full or defaults[name] % 128 == 0
