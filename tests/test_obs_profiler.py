"""``repro.obs`` spans on the JAX profiler's trace, and the serving path's
spans: every span also enters ``jax.profiler.TraceAnnotation``, so under a
profiler session it lands on the ``/host:CPU`` plane beside the device
trace, whether or not the JSON sink is on."""
from __future__ import annotations

import glob
import os
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro import obs
from repro.core import ChoiceDim, SearchSpace
from repro.launch import serve
from repro.obs.trace import Tracer
from repro.runtime import ContextRouter, DriftDetector


@pytest.fixture(autouse=True)
def _obs_off():
    obs.shutdown()
    yield
    obs.shutdown()


def _host_spans(trace_dir) -> list:
    """(name, start_ns, end_ns, line) of every event on ``/host:CPU``."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns, line.name)
                        for e in line.events]
    return out


def test_span_lands_on_the_profiler_host_plane_only_while_it_records(tmp_path):
    with obs.span("unit.before"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("unit.outer"):
            with obs.span("unit.inner", cat="serve"):
                jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    with obs.span("unit.after"):
        pass
    spans = {n: (s, t, line) for n, s, t, line in _host_spans(tmp_path)}
    assert {"unit.outer", "unit.inner"} <= set(spans)
    assert "unit.before" not in spans and "unit.after" not in spans
    (os_, ot, oline), (is_, it, iline) = spans["unit.outer"], spans["unit.inner"]
    assert os_ <= is_ <= it <= ot and oline == iline


def test_json_sink_and_profiler_record_the_same_spans(tmp_path):
    obs.configure(str(tmp_path / "obs"))
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        with obs.span("unit.both") as s:
            assert obs.current_span() is s
    finally:
        jax.profiler.stop_trace()
    assert [x.name for x in obs.tracer().finished()] == ["unit.both"]
    assert "unit.both" in {n for n, *_ in _host_spans(tmp_path / "prof")}


def test_disabled_tracer_span_is_the_annotation_alone():
    t = Tracer()
    s = t.span("anything")
    assert isinstance(s, TraceAnnotation)
    with s:
        assert t.current() is None
    assert t.finished() == []


def test_wrap_opens_worker_spans_while_only_the_profiler_records(tmp_path):
    t = Tracer()
    assert t.wrap(abs, "compile") is abs
    jax.profiler.start_trace(str(tmp_path))
    try:
        work = t.wrap(lambda x: x * 2, "unit.compile")
        assert work is not abs
        with ThreadPoolExecutor(2) as pool:
            assert list(pool.map(work, [1, 2, 3])) == [2, 4, 6]
    finally:
        jax.profiler.stop_trace()
    assert sum(1 for n, *_ in _host_spans(tmp_path) if n == "unit.compile") == 3
    assert t.finished() == []


def test_run_chunk_spans_the_dispatch_and_the_wait(tmp_path):
    @jax.jit
    def fn(params, token, states, pos):
        return token + 1, states * 2, pos + 1, (token * params)[None]

    args = (jnp.int32(3), jnp.int32(1), jnp.ones(4), jnp.int32(0))
    jax.block_until_ready(fn(*args))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("decode_chunk"):
            token, states, pos, toks = serve.run_chunk(fn, *args)
    finally:
        jax.profiler.stop_trace()
    assert (int(token), float(states.sum()), int(pos), int(toks[0])) == (2, 8.0, 1, 3)
    spans = {n: (s, t) for n, s, t, _ in _host_spans(tmp_path)}
    d, w, c = spans["serve.dispatch"], spans["serve.wait"], spans["decode_chunk"]
    assert c[0] <= d[0] <= d[1] <= w[0] <= w[1] <= c[1]


def test_tuner_spans_nest_under_begin_and_observe(tmp_path):
    obs.configure(str(tmp_path / "obs"))
    space = SearchSpace([ChoiceDim("k", (1, 2, 4, 8))])
    router = ContextRouter()
    router.register("unit/k", space=lambda: space, defaults=lambda: {"k": 1},
                    epsilon=1.0, num_opt=2, max_iter=2,
                    drift={"window": 2, "min_samples": 2, "factor": 1.5})
    tuner = router.tuner("unit/k")
    calls = 0
    while not tuner.finished and calls < 200:
        d = router.begin("unit/k")
        router.observe(d, float(d.point["k"]))
        calls += 1
    assert tuner.finished
    for cost in (1.0, 1.0, 9.0, 9.0):  # baseline, then a drift
        router.observe(router.begin("unit/k"), cost)
    spans = obs.tracer().finished()
    by_id = {s.span_id: s for s in spans}
    names = [s.name for s in spans]
    assert names.count("tuner.begin") == names.count("tuner.observe") == calls + 4
    assert "request" not in names
    for child in ("tuner.tell", "tuner.commit", "drift.observe", "tuner.drift_reset"):
        found = [s for s in spans if s.name == child]
        assert found, child
        assert all(by_id[s.parent_id].name == "tuner.observe" for s in found)
    assert names.count("tuner.commit") == tuner.stats()["searches_completed"] == 1


def test_drift_observe_span_wraps_the_detector():
    dd = DriftDetector(window=2, min_samples=2, factor=1.5)
    t = obs.tracer()
    t.reset()
    t.enable()
    try:
        levels = [dd.observe(c) for c in (1.0, 1.0, 2.0, 2.0)]
    finally:
        t.disable()
    assert levels == [0, 0, 0, 1]
    assert [s.name for s in t.finished()] == ["drift.observe"] * 4
    t.reset()
