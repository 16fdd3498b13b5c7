"""``chipbench/trace.py`` on a small trace recorded on a TPU v5e (the
closed-batch engine serving a 2-layer, 256-wide Qwen2-shaped model for
12 ms under the profiler, ``data/engine_tiny.xplane.pb``) and on a
hand-made trace with known answers."""
from pathlib import Path

import pytest

from chipbench import run, trace
from chipbench.trace import Trace

RECORDED = Path(__file__).parent / "data" / "engine_tiny.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    tr = trace.load(str(RECORDED), run.SPANS + (trace.WINDOW_SPAN,))
    return tr, trace.reduce(tr, trace.window(tr, trace.WINDOW_SPAN))


def test_recorded_trace_has_device_work_and_host_spans(recorded):
    tr, _ = recorded
    assert list(tr.ops) == ["/device:TPU:0"]
    names = {m[2] for m in tr.modules}
    assert {"jit_prefill_first_token", "jit_run"} <= names
    spans = {s[2] for s in tr.spans}
    assert {"decode_chunk", "router.begin", "router.observe", trace.WINDOW_SPAN} <= spans
    # every op lies inside the program run it belongs to
    assert all(op[2].split(":")[0] in names for op in tr.ops["/device:TPU:0"])
    # the host sees a run end after the device ended it
    assert tr.offset_ns > 0


def test_recorded_trace_reduction(recorded):
    tr, red = recorded
    lo, hi = trace.window(tr, trace.WINDOW_SPAN)
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    gap_s = sum(v for _, v in red["idle_gaps"])
    # the gaps (all of them, when there are at most ten labels) and the busy
    # time tile the window
    assert len(red["idle_gaps"]) < 10
    assert gap_s + red["busy_s"] == pytest.approx(red["window_s"], rel=1e-9)
    assert {n for n, _ in red["idle_gaps"]} <= set(run.SPANS) | {"host"}
    assert sum(v for _, v in red["device_ops"]) <= red["busy_s"] * (1 + 1e-9)
    assert all(v > 0 for _, v in red["device_ops"])
    assert set(red["module_s"]) == {m[2] for m in tr.modules}


def test_reduction_by_hand():
    # device: a while loop [10, 40) holding two body ops, then an op [60, 70);
    # host spans (offset 5): decode_chunk [12, 50) and router.begin [52, 64)
    ops = {"/device:TPU:0": [(10, 40, "jit_run:while"), (10, 20, "jit_run:body"),
                             (25, 35, "jit_run:body"), (60, 70, "jit_p:fusion")]}
    mods = [(10, 40, "jit_run", "/device:TPU:0"), (60, 70, "jit_p", "/device:TPU:0")]
    spans = [(5, 95, trace.WINDOW_SPAN), (12, 50, "decode_chunk"), (52, 64, "router.begin")]
    tr = Trace(ops, mods, spans, 5.0)
    lo, hi = trace.window(tr, trace.WINDOW_SPAN)
    assert (lo, hi) == (0, 90)
    red = trace.reduce(tr, (lo, hi))
    assert red["busy_s"] == pytest.approx(40e-9)
    assert red["window_s"] == pytest.approx(90e-9)
    assert dict(red["device_ops"]) == pytest.approx(
        {"jit_run:while": 10e-9, "jit_run:body": 20e-9, "jit_p:fusion": 10e-9})
    # gaps: [0,10) mid 5 -> host 10: host; [40,60) mid 50 -> host 55: router.begin;
    # [70,90) mid 80 -> host 85: host
    assert dict(red["idle_gaps"]) == pytest.approx({"host": 30e-9, "router.begin": 20e-9})
    assert red["module_s"] == pytest.approx({"jit_run": 30e-9, "jit_p": 10e-9})
