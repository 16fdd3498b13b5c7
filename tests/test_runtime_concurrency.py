"""Multi-threaded serving-runtime stress tests (the ``concurrency`` marker).

Threads are released together through a :class:`threading.Barrier` so every
test maximizes real interleaving, and every assertion is on an *accounting
identity* rather than a trajectory — under true concurrency the interleaving
is non-deterministic, but the books must balance at every consistent read
point:

* ``calls == explores + exploits`` (per tuner, and per aggregate),
* ``explores == explore_reps_decided + stale_explore_reps + buffered``,
* per-tenant ε-credit: no tenant's explores exceed ε of its own calls +1,
* one build per (point, signature): racing streams never duplicate an
  in-flight compile,
* the router's dispatch snapshot yields exactly one tuner per context no
  matter how many threads race the first sight of a signature.
"""
import threading

import pytest

from repro.core import CSA, Autotuning, ExecutableCache, IntDim, SearchSpace
from repro.core.measure import MeasurePolicy
from repro.runtime import EXPLORE, ContextRouter, OnlineTuner

pytestmark = pytest.mark.concurrency

THREADS = 8


def _space(hi=32):
    return SearchSpace([IntDim("p", 1, hi)])


def _at(space=None, num_opt=3, max_iter=4, seed=0, **kw):
    space = space or _space()
    return Autotuning(
        space=space, ignore=0,
        search=CSA(len(space), num_opt=num_opt, max_iter=max_iter, seed=seed),
        cache=True, **kw,
    )


def _hammer(fn, n_threads=THREADS, reps=60):
    """Run ``fn(thread_index, rep_index)`` from ``n_threads`` threads released
    simultaneously; re-raises the first worker exception."""
    barrier = threading.Barrier(n_threads)
    errors = []

    def work(i):
        barrier.wait()
        try:
            for r in range(reps):
                fn(i, r)
        except BaseException as e:  # noqa: BLE001 - reported to the test
            errors.append(e)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


# ----------------------------------------------------------- ε / accounting
def test_tenant_epsilon_accounting_under_contention():
    """Concurrent tenants each stay within their own ε budget, and the
    global identities hold after the storm."""
    t = OnlineTuner(_at(max_iter=50), epsilon=0.25)
    eps = t.epsilon

    def serve(i, r):
        d = t.begin(tenant=f"tenant-{i}")
        cost = float((d.point["p"] - 9) ** 2) if d.kind == EXPLORE else 1.0
        t.observe(d, cost)

    _hammer(serve, reps=80)
    s = t.stats()
    assert s["calls"] == THREADS * 80
    assert s["calls"] == s["explores"] + s["exploits"]
    assert s["explores"] == (
        s["explore_reps_decided"] + s["stale_explore_reps"]
        + s["explore_reps_buffered"]
    )
    # the search converged at some point mid-storm, clearing the per-tenant
    # episode counters — only tenants still live in the table are checkable,
    # but for those the credit rule must hold exactly
    for tenant, ts in s.get("tenants", {}).items():
        assert ts["explores"] <= eps * ts["calls"] + 1, (tenant, ts)


def test_snapshot_identities_hold_mid_update():
    """A reader thread polling ``snapshot()`` mid-storm must never see torn
    counters: the identities hold at every single read."""
    t = OnlineTuner(_at(max_iter=200), epsilon=0.5)
    stop = threading.Event()
    bad = []

    def reader():
        while not stop.is_set():
            snap = t.snapshot()
            if snap["calls"] != snap["explores"] + snap["exploits"]:
                bad.append(("calls", snap))
            reps = (snap["explore_reps_decided"] + snap["stale_explore_reps"]
                    + snap["explore_reps_buffered"] + snap["explore_inflight"])
            if snap["explores"] != reps:
                bad.append(("reps", snap))

    poller = threading.Thread(target=reader)
    poller.start()
    try:
        def serve(i, r):
            d = t.begin()
            t.observe(d, float(d.point["p"]) if d.kind == EXPLORE else 1.0)

        _hammer(serve, reps=100)
    finally:
        stop.set()
        poller.join()
    assert not bad, bad[:3]


def test_rung_accounting_under_cross_stream_racing():
    """With a measurement policy, racing streams share one candidate rung;
    every explore request resolves to exactly one of decided/stale/buffered."""
    policy = MeasurePolicy(mode="fixed", repeats=3)
    t = OnlineTuner(_at(max_iter=30), epsilon=1.0, measure=policy)

    def serve(i, r):
        d = t.begin()
        t.observe(d, float((d.point["p"] - 5) ** 2) if d.kind == EXPLORE else 1.0)

    _hammer(serve, reps=60)
    s = t.stats()
    assert s["calls"] == s["explores"] + s["exploits"]
    assert s["explores"] == (
        s["explore_reps_decided"] + s["stale_explore_reps"]
        + s["explore_reps_buffered"]
    )
    # fixed repeats=3: every decided candidate consumed at most 3 reps
    if s["explore_candidates"]:
        assert s["explore_reps_decided"] <= 3 * s["explore_candidates"]


def test_cross_stream_racing_matches_the_serial_search():
    """Streams that race one context's rungs reach the serial search's best
    point within its request count, give or take the one request per
    stream already in flight when the search finishes."""
    import time

    policy = MeasurePolicy(mode="fixed", repeats=3)

    def tuner():
        router = ContextRouter()
        router.register("race", space=lambda *a, **k: _space(16),
                        defaults=lambda *a, **k: {"p": 4}, epsilon=1.0,
                        num_opt=3, max_iter=3, measure=policy)
        return router.tuner("race", extra={"shape": 0})

    def cost(point):
        return 1.0 + 0.05 * (point["p"] - 11) ** 2

    serial = tuner()
    serial_calls = 0
    while not serial.finished:
        d = serial.begin()
        serial.observe(d, cost(d.point))
        serial_calls += 1

    racing = tuner()
    calls = [0] * THREADS

    def serve(i, r):
        if racing.finished:
            return
        d = racing.begin(tenant=f"t{i}")
        racing.observe(d, cost(d.point))
        calls[i] += 1
        time.sleep(1e-4)  # the request's serving work, so streams interleave

    _hammer(serve, reps=serial_calls + 1)
    assert racing.finished
    assert racing.best_point == serial.best_point
    assert sum(calls) <= serial_calls + THREADS


# ------------------------------------------------------------------- builds
def test_no_duplicate_inflight_builds_per_signature():
    """Racing threads asking for the same (point, signature) executable get
    one build, not one per thread — the cache's future is the dedup point."""
    calls = []
    lock = threading.Lock()
    started = threading.Barrier(THREADS, timeout=10)

    def build(key):
        with lock:
            calls.append(key)
        return f"exe-{key}"

    cache = ExecutableCache(maxsize=64)

    def hit(i, r):
        if r == 0:
            started.wait()  # all threads reach the first build together
        key = ("point", r % 4)
        got = cache.get_or_build(key, lambda k=key: build(k))
        assert got == f"exe-{key}"

    _hammer(hit, reps=40)
    assert len(calls) == 4  # one build per distinct key, ever
    st = cache.stats()
    assert st["misses"] == 4
    assert st["hits"] == THREADS * 40 - 4


def test_cache_eviction_caps_under_concurrent_build():
    """LRU caps hold under concurrent insertion and evictions are counted."""
    cache = ExecutableCache(maxsize=256, max_entries=8)

    def hit(i, r):
        key = (i, r)
        cache.get_or_build(key, lambda: b"x" * 64)

    _hammer(hit, reps=50)
    st = cache.stats()
    assert st["size"] <= 8
    assert st["misses"] == THREADS * 50  # distinct keys: no dedup expected
    assert st["evictions"] == st["misses"] - st["size"]


# ------------------------------------------------------------------- router
def test_router_creates_one_context_per_signature_under_racing():
    """All threads hitting a cold router converge on the same tuner objects;
    the dispatch snapshot never yields duplicates or loses contexts."""
    router = ContextRouter()
    router.register("ctx", space=lambda *a, **k: _space(), epsilon=0.25,
                    max_iter=10)
    seen = [set() for _ in range(4)]
    lock = threading.Lock()

    def serve(i, r):
        shape = r % 4  # four distinct contexts, all racing
        t = router.tuner("ctx", extra={"shape": shape})
        with lock:
            seen[shape].add(id(t))
        d = router.begin("ctx", extra={"shape": shape}, tenant=f"t{i}")
        router.observe(d, float(d.point["p"]) if d.kind == EXPLORE else 1.0)

    _hammer(serve, reps=40)
    for shape, ids in enumerate(seen):
        assert len(ids) == 1, f"context {shape} duplicated: {ids}"
    s = router.stats()
    assert s["contexts"] == 4
    assert s["calls"] == THREADS * 40
    assert s["calls"] == s["explores"] + s["exploits"]


def test_router_fast_path_is_stable_across_snapshot_swaps():
    """Threads creating new contexts (snapshot swaps) never disturb threads
    riding the fast path of an existing context."""
    router = ContextRouter()
    router.register("hot", space=lambda *a, **k: _space(), epsilon=0.0)
    router.register("cold", space=lambda *a, **k: _space(), epsilon=0.0)
    hot = router.tuner("hot", extra={"k": 0})

    def serve(i, r):
        if i % 2 == 0:
            # fast-path rider: must always resolve to the same tuner
            assert router.tuner("hot", extra={"k": 0}) is hot
        else:
            # snapshot churner: a fresh context every few reps
            router.tuner("cold", extra={"k": (i, r)})

    _hammer(serve, reps=50)
    assert router.tuner("hot", extra={"k": 0}) is hot
    # half the threads created 50 contexts each, plus "hot"
    assert router.stats()["contexts"] == (THREADS // 2) * 50 + 1


def test_wait_pending_does_not_deadlock_with_serving_threads():
    """``wait_pending`` waits outside the tuner lock, so serving threads and
    background builds make progress while another thread drains."""
    space = _space(8)

    def build(point, *args, **kwargs):
        return ("exe", point["p"])

    t = OnlineTuner(_at(space, max_iter=10), epsilon=0.5, build=build, jobs=2)
    done = threading.Event()

    def drainer():
        while not done.is_set():
            t.wait_pending(timeout=0.05)

    dr = threading.Thread(target=drainer)
    dr.start()
    try:
        def serve(i, r):
            d = t.begin(1, r % 4)
            t.observe(d, float(d.point["p"]) if d.kind == EXPLORE else 1.0)

        _hammer(serve, n_threads=4, reps=40)
    finally:
        done.set()
        dr.join(timeout=10)
    assert not dr.is_alive()
    assert t.stats()["inband_builds"] == 0  # builds never ran on a server thread
