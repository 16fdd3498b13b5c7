"""``chipbench/layers.py``, ``layer_counts.py`` and ``layer_metrics.py``:
the program's named scopes in its compiled HLO, the join from a traced run
to its program, the overlap split of idle time by host span, and the
readings of ``chipbench/trace.py`` they leave unchanged; on tiny programs
compiled for the CPU and on hand-made traces with known answers."""
import collections
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from bench_helpers import PEAKS, tiny_cell

from chipbench import catalog, counts, layer_counts, layer_metrics, layers, record, run, trace
from chipbench.trace import Trace
from repro.launch import serve
from repro.models import ExecConfig, Model

RECORDED = Path(__file__).parent / "data" / "engine_tiny.xplane.pb"


@pytest.fixture(scope="module")
def tiny_programs():
    """The tiny Qwen2 cell's prefill of two prompt lengths (as the engine
    jits it) and its decode chunk of every k, compiled for the CPU."""
    cell = tiny_cell("qwen2_7b", 1.0)
    cfg = catalog.model_config(cell["config_file"])
    model = Model(cfg, ExecConfig(rec_chunk=4))
    params = model.init(jax.random.PRNGKey(0))

    def prefill_first_token(params, tokens):
        hidden, states = model.prefill(params, {"tokens": tokens, "max_len": 28})
        logits = model.logits(params, hidden[:, None])[:, 0]
        return jnp.argmax(logits, -1).astype(jnp.int32)[:, None], states

    fn = jax.jit(prefill_first_token)
    prefill = {P: fn.lower(params, jnp.zeros((2, P), jnp.int32)).compile() for P in (8, 16)}
    token, states = prefill[8](params, jnp.zeros((2, 8), jnp.int32))
    decode = {k: serve.decode_chunk(model, k).lower(params, token, states, jnp.int32(8)).compile()
              for k in range(1, 9)}
    return prefill, decode


def _instructions(hlo: str):
    """(computation, op, own scope, is a matmul, called computations)."""
    comp = None
    for line in hlo.splitlines():
        m = layers._COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = layers._INSTR.match(line)
        if m and comp:
            op, rhs = m.groups()
            on = layers._OP_NAME.search(rhs)
            yield (comp, op, layers.scope_of(on.group(1)) if on else layers.UNSCOPED,
                   bool(layers._MATMUL.search(rhs)), layers._CALLS.findall(rhs))


def _holder(hlo: str) -> dict:
    """Each instruction -> the top-level instruction that runs it (itself
    where it is not inside a fusion)."""
    ins = list(_instructions(hlo))
    caller = {c: op for _, op, _, _, calls in ins for c in calls}
    out = {}
    for comp, op, *_ in ins:
        top, c = op, comp
        while c in caller and "fused" in c:
            top = caller[c]
            c = next(cc for cc, o, *_ in ins if o == top)
        out[op] = top
    return out


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_hlo_carries_the_model_scopes(tiny_programs, program):
    prefill, decode = tiny_programs
    ex = prefill[8] if program == "prefill" else decode[2]
    hlo = ex.as_text()
    smap = layers.scope_map(hlo)
    ins = list(_instructions(hlo))
    holder = _holder(hlo)
    # every matmul lies in a block or head scope; each of the FFN's three
    # matmuls (gate, up, down) and everything running it maps to ffn
    dots = [(op, scope) for _, op, scope, mm, _ in ins if mm]
    assert {s for _, s in dots} == {"attn", "ffn", "lm_head"}
    ffn = [op for op, s in dots if s == "ffn"]
    assert len(ffn) == 3
    assert all(smap[holder[op]] == "ffn" for op in ffn)
    # the cache update: each dynamic-update-slice under kv_write runs in an
    # op mapped to kv_write or, where the compiler fused the write into the
    # layer scan's stacking of the cache, to layers
    writes = [op for _, op, scope, _, _ in ins if scope == "kv_write"
              and re.search(r"dynamic.update.slice", op)]
    assert len(writes) >= 2
    held = {smap[holder[op]] for op in writes}
    assert "kv_write" in held and held <= {"kv_write", "layers"}
    scopes = set(smap.values())
    assert {"embed", "layers", "attn", "kv_write", "ffn", "norm", "lm_head"} <= scopes
    if program == "decode":
        # the argmax over the vocabulary: a reduce that runs under sample
        argmax = [op for _, op, scope, _, _ in ins if scope == "sample" and "reduce" in op]
        assert argmax and {smap[holder[op]] for op in argmax} == {"sample"}
    else:
        assert "sample" not in scopes  # the engine's own argmax has no scope


def test_join_tells_every_program_apart(tiny_programs):
    prefill, decode = tiny_programs
    programs = [layers.describe(ex.as_text()) for ex in [*prefill.values(), *decode.values()]]
    texts = {P: ex.as_text() for P, ex in prefill.items()}
    # each decode chunk size runs under a module name of its own; the
    # prefills of two prompt lengths share one, and the shapes of their ops
    # tell which ran
    dims = {"jit_prefill_first_token(11)": layers.op_dims(texts[16]),
            "jit_prefill_first_token(22)": layers.op_dims(texts[8]),
            **{f"jit_decode_k{k}(3{k})": layers.op_dims(ex.as_text()) for k, ex in decode.items()}}
    joined = layers.join(dims, programs)
    assert joined["jit_prefill_first_token(11)"] == layers.scope_map(texts[16])
    assert joined["jit_prefill_first_token(22)"] == layers.scope_map(texts[8])
    for k, ex in decode.items():
        assert joined[f"jit_decode_k{k}(3{k})"] == layers.scope_map(ex.as_text())
    # a run whose ops fit two programs alike is not guessed
    both = layers.join({"jit_prefill_first_token(7)": {}}, programs)
    assert set(both["jit_prefill_first_token(7)"].values()) <= {layers.AMBIGUOUS}
    assert layers.join({"jit_other(1)": {"x": "1"}}, programs) == {}


def test_scope_of_takes_the_innermost_scope():
    assert layers.scope_of("jit(run)/while/body/layers/while/body/closed_call/attn/kv_write/"
                           "dynamic_update_slice") == "kv_write"
    assert layers.scope_of("jit(run)/while/body/layers/while/body/dynamic_slice") == "layers"
    assert layers.scope_of("jit(run)/while") == layers.UNSCOPED


def test_scope_map_by_hand():
    hlo = """HloModule jit_run, is_scheduled=true

%fused_computation.1 (p: f32[4,4]) -> f32[4,4] {
  %p = f32[4,4]{1,0} parameter(0)
  %dot.1 = f32[4,4]{1,0} dot(%p, %p), metadata={op_name="jit(run)/layers/while/body/ffn/dot_general"}
  ROOT %add.1 = f32[4,4]{1,0} add(%dot.1, %p), metadata={op_name="jit(run)/layers/while/body/add"}
}

%fused_computation.2 (p: f32[4,4]) -> f32[4,4] {
  %p.2 = f32[4,4]{1,0} parameter(0)
  ROOT %mul.2 = f32[4,4]{1,0} multiply(%p.2, %p.2), metadata={op_name="jit(run)/norm/mul"}
}

ENTRY %main.3 (x: f32[4,4]) -> f32[4,4] {
  %x = f32[4,4]{1,0} parameter(0)
  %convolution_add_fusion = f32[4,4]{1,0} fusion(%x), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(run)/layers/while/body/add"}
  %multiply_fusion = f32[4,4]{1,0} fusion(%convolution_add_fusion), kind=kLoop, calls=%fused_computation.2
  ROOT %copy.3 = f32[4,4]{1,0} copy(%multiply_fusion)
}
"""
    smap = layers.scope_map(hlo)
    assert smap["convolution_add_fusion"] == "ffn"  # the matmul decides
    assert smap["multiply_fusion"] == "norm"  # no op_name: its root decides
    assert smap["copy.3"] == layers.UNSCOPED
    assert smap["dot.1"] == "ffn" and smap["add.1"] == "layers"


def _hand_trace():
    """Device (ns): module A runs [10, 50) holding a while [10, 40) with
    two body ops, then an op [42, 48); module B runs [70, 80) with one op.
    Host spans (offset 5): decode_chunk [12, 60) holding serve.dispatch
    [12, 20) and serve.wait [20, 58); router.begin [62, 90) holding
    tuner.begin [64, 86)."""
    ops = {"/device:TPU:0": [(10, 40, "jit_a:while"), (10, 20, "jit_a:body.a"),
                             (25, 35, "jit_a:body.b"), (42, 48, "jit_a:out"),
                             (70, 80, "jit_b:fusion")]}
    mods = [(10, 50, "jit_a", "/device:TPU:0"), (70, 80, "jit_b", "/device:TPU:0")]
    spans = [(5, 105, trace.WINDOW_SPAN), (12, 60, "decode_chunk"), (12, 20, "serve.dispatch"),
             (20, 58, "serve.wait"), (62, 90, "router.begin"), (64, 86, "tuner.begin")]
    return Trace(ops, mods, sorted(spans), 5.0)


def test_scope_time_by_hand():
    tr = _hand_trace()
    scopes = {"jit_a": {"while": "layers", "body.a": "ffn", "body.b": "attn", "out": "sample"},
              "jit_b": {"fusion": "attn"}}
    st = layers.scope_time(tr, scopes)
    assert st["jit_a"] == pytest.approx(
        {"layers": 10e-9, "ffn": 10e-9, "attn": 10e-9, "sample": 6e-9, layers.BETWEEN: 4e-9})
    assert st["jit_b"] == pytest.approx({"attn": 10e-9, layers.BETWEEN: 0.0})
    # a module's scopes sum to its run time, as trace.reduce counts it
    module_s = trace.reduce(tr, (0, 100))["module_s"]
    assert {m: sum(v.values()) for m, v in st.items()} == pytest.approx(module_s)
    # an op its module's map lacks counts under "?"
    assert layers.scope_time(tr, {})["jit_b"]["?"] == pytest.approx(10e-9)


def test_idle_split_by_overlap_by_hand():
    tr = _hand_trace()
    win = (0, 100)  # device ns; host 5..105
    idle = layers.idle_by_span(tr, win)
    # idle device intervals [0,10) [40,42) [48,70) [80,100) are host
    # [5,15) [45,47) [53,75) [85,105)
    assert idle == pytest.approx({
        "host": ((12 - 5) + (62 - 60) + (105 - 90)) * 1e-9,
        "serve.dispatch": (15 - 12) * 1e-9,
        "serve.wait": ((47 - 45) + (58 - 53)) * 1e-9,
        "decode_chunk": (60 - 58) * 1e-9,
        "router.begin": ((64 - 62) + (90 - 86)) * 1e-9,
        "tuner.begin": ((75 - 64) + (86 - 85)) * 1e-9,
    })
    red = trace.reduce(tr, win)
    assert sum(idle.values()) + red["busy_s"] == pytest.approx(red["window_s"])
    # the midpoint rule gives each gap whole to one span instead
    assert dict(red["idle_gaps"]) == pytest.approx(
        {"host": 30e-9, "serve.wait": 2e-9, "tuner.begin": 22e-9})
    assert layers.span_count(tr, "tuner.begin", win) == 1
    assert layers.span_count(tr, "serve.dispatch", (10, 100)) == 0  # host 12 is device 7


def test_segments_give_each_instant_to_the_innermost_span():
    spans = [(0, 100, "a"), (10, 20, "b"), (12, 15, "c"), (20, 30, "d"), (40, 120, "e")]
    segs = layers._segments(spans)
    assert segs == [(0, 10, "a"), (10, 12, "b"), (12, 15, "c"), (15, 20, "b"), (20, 30, "d"),
                    (30, 40, "a"), (40, 100, "e")]


def test_span_at_look_back_finds_the_open_span_under_program_spans():
    """``trace._span_at`` looks at the last 8 spans that started; with the
    program's spans nested in the engine's (a routed chunk opens nine) it
    still finds the innermost open one."""
    spans, t = [(0, 10_000, trace.WINDOW_SPAN)], 10
    for _ in range(20):  # one routed chunk after another
        spans += [(t, t + 5, "router.begin"), (t + 1, t + 4, "tuner.begin"),
                  (t + 6, t + 40, "decode_chunk"), (t + 6, t + 9, "serve.dispatch"),
                  (t + 9, t + 38, "serve.wait"), (t + 41, t + 60, "router.observe"),
                  (t + 42, t + 59, "tuner.observe"), (t + 43, t + 50, "tuner.tell"),
                  (t + 51, t + 58, "tuner.commit")]
        t += 70
    tr = Trace({}, [], sorted(spans), 0.0)

    def innermost(x):
        open_ = [s for s in tr.spans if s[0] <= x <= s[1] and s[2] != trace.WINDOW_SPAN]
        return max(open_)[2] if open_ else "host"

    for x in range(0, t + 10):
        x += 0.5
        assert trace._span_at(tr, x) == innermost(x), x


@pytest.mark.parametrize("cfg", [
    catalog.cell("qwen2_7b.decode")["config_file"]["model_config"],
    tiny_cell("qwen2_7b", 1.0)["config_file"]["model_config"]], ids=["qwen2_7b", "tiny"])
def test_layer_counts_add_up_to_the_step(cfg):
    d, H, Kh, hd, ff, V = counts._dims(cfg)
    pb = counts._BYTES[cfg["param_dtype"]]
    L = cfg["n_layers"]
    for B, pos in ((32, 512), (1, 4095)):
        parts = (layer_counts.ffn_weight_bytes(cfg) + layer_counts.attn_decode_bytes(cfg, B, pos)
                 + layer_counts.head_weight_bytes(cfg) + (2 * L + 1) * d * pb + B * d * pb)
        assert parts == counts.decode_step_bytes(cfg, B, pos)
    for B, S in ((1, 1024), (2, 4096)):
        rest = B * (2 * 3 * d * ff * S * L + 2 * d * V)
        assert layer_counts.attn_prefill_flops(cfg, B, S) + rest == counts.prefill_flops(cfg, B, S)


def test_layer_metrics_by_hand():
    cfg = tiny_cell("qwen2_7b", 1.0)["config_file"]["model_config"]
    scope_s = {"jit_decode_k1": {"ffn": 2.0, "attn": 1.0, "kv_write": 1.0, "lm_head": 0.5,
                              "sample": 0.5, "layers": 1.0, layers.BETWEEN: 4.0},
               "jit_prefill_first_token": {"attn": 3.0, "ffn": 1.0}}
    positions, bw = [8, 9, 10], PEAKS["hbm_bytes_per_s"]
    m = layer_metrics.decode_metrics(
        scope_s, {"serve.dispatch": 0.002, "serve.wait": 0.004, "tuner.begin": 0.001,
                  "drift.observe": 0.001, "decode_chunk": 1.0},
        modules={"jit_decode_k1", "jit_decode_k2"}, cfg=cfg, batch=2, positions=positions,
        peaks=PEAKS, chunks=3, routed=2)
    assert m["ffn_roofline.decode"] == pytest.approx(
        100 * 3 * layer_counts.ffn_weight_bytes(cfg) / bw / 2.0)
    assert m["attn_roofline.decode"] == pytest.approx(
        100 * sum(layer_counts.attn_decode_bytes(cfg, 2, p) for p in positions) / bw / 2.0)
    assert m["lm_head_roofline.decode"] == pytest.approx(
        100 * 3 * layer_counts.head_weight_bytes(cfg) / bw / 1.0)
    assert m["layer_state_share.decode"] == pytest.approx(10.0)
    assert m["dispatch_idle_ms.decode"] == pytest.approx(2.0)
    assert m["dispatch_idle_ms.decode.dispatch"] == pytest.approx(2 / 3)
    assert m["router_idle_ms.decode"] == pytest.approx(1.0)
    assert layer_metrics.attn_mfu(scope_s, {"jit_prefill_first_token"}, cfg, 1, [16], PEAKS) == \
        pytest.approx(100 * layer_counts.attn_prefill_flops(cfg, 1, 16) / PEAKS["flops_bf16"] / 3.0)
    # a trace with no scopes (a program without them) reads None
    none = layer_metrics.decode_metrics({"jit_run": {layers.UNSCOPED: 1.0}}, {},
                                        modules={"jit_run"}, cfg=cfg, batch=2,
                                        positions=positions, peaks=PEAKS, chunks=3, routed=2)
    assert all(v is None for k, v in none.items() if k != "layer_state_share.decode")


def test_existing_readings_of_the_recorded_trace_are_unchanged():
    """``trace.reduce`` and the readers of ``BENCHMARK.json`` on the trace
    recorded before the program had spans or scopes read what they read
    when it was recorded."""
    tr = trace.load(str(RECORDED), run.SPANS + (trace.WINDOW_SPAN,))
    red = trace.reduce(tr, trace.window(tr, trace.WINDOW_SPAN))
    assert red["busy_s"] == pytest.approx(0.000491446, rel=1e-12)
    assert red["window_s"] == pytest.approx(0.01270429, rel=1e-12)
    assert [n for n, _ in red["device_ops"][:3]] == [
        "jit_run:dynamic_update_slice.41", "jit_run:dynamic_update_slice.42", "jit_run:fusion.136"]
    assert [v for _, v in red["device_ops"][:3]] == pytest.approx(
        [5.4545e-05, 5.4542e-05, 3.7191e-05], rel=1e-12)
    assert dict(red["idle_gaps"]) == pytest.approx({
        "decode_chunk": 0.004695485, "new_job": 0.004679546, "host": 0.002411178,
        "prefill": 0.000426635}, rel=1e-12)
    assert red["module_s"] == pytest.approx(
        {"jit_run": 0.000443736, "jit_prefill_first_token": 5.3744e-05}, rel=1e-12)
    # the same file read with the program's span names as well: the busy
    # time, the ops and the program times stay; the file has none of them
    tr2 = trace.load(str(RECORDED), run.SPANS + layers.PROGRAM_SPANS + (trace.WINDOW_SPAN,))
    assert trace.reduce(tr2, trace.window(tr2, trace.WINDOW_SPAN)) == red
    Job = collections.namedtuple("Job", "chunks prompts t_start t_first")
    Chunk = collections.namedtuple("Chunk", "k t0 t1 pos explore")
    jobs = [Job([Chunk(1, 1.0, 1.1, 9, False), Chunk(2, 1.2, 1.3, 10, True)],
                jnp.zeros((2, 8)), 0.9, 0.95)]
    rec = record.RunRecord(cell={"name": "qwen2_7b.decode"}, batch=2, peaks=PEAKS, setup_s=1.0,
                           model_config=tiny_cell("qwen2_7b", 1.0)["config_file"]["model_config"],
                           window=(0.0, 2.0), jobs=jobs, traced=(0.0, 2.0), trace=red,
                           router_stats={"explores": 1, "exploits": 3},
                           prefill_modules={"jit_prefill_first_token"},
                           decode_modules={"jit_run"})
    read = {m: catalog.reader(m)(rec) for m in
            ("idle_share.decode", "idle_share.prefill", "mfu.decode", "decode_step_roofline",
             "mfu.prefill", "explore_share.decode")}
    steps = [9, 10, 11]
    assert read == pytest.approx({
        "idle_share.decode": 100 * (1 - 0.000491446 / 0.01270429),
        "idle_share.prefill": 100 * (1 - 0.000491446 / 0.01270429),
        "mfu.decode": 100 * sum(counts.decode_step_flops(rec.model_config, 2, p) for p in steps)
        / (0.000443736 * PEAKS["flops_bf16"]),
        "decode_step_roofline": 100 * sum(counts.decode_step_bytes(rec.model_config, 2, p)
                                          for p in steps) / PEAKS["hbm_bytes_per_s"] / 0.000443736,
        "mfu.prefill": 100 * counts.prefill_flops(rec.model_config, 2, 8)
        / (5.3744e-05 * PEAKS["flops_bf16"]),
        "explore_share.decode": 25.0}, rel=1e-12)


def test_layer_report_runs_the_cell_through_run_chunk_on_the_cpu():
    """The report at tiny size on the CPU: the engine serves through
    ``serve.run_chunk``; the CPU trace has no device plane, so every metric
    reads None while the host spans are there."""
    from chipbench import layer_report

    cell = tiny_cell("qwen2_7b", 1.0)
    out = layer_report.report(cell, 2**40 + 5, 0.3, 0.05, PEAKS)
    assert all(v is None for v in out["metrics"].values())
    assert out["per_token_ms"]["measured"]["chunks"] > 0
    assert out["per_token_ms"]["traced"]["chunks"] > 0
    assert out["setup_s"] > 0 and out["router_stats"]["explores"] > 0


SCOPED = Path(__file__).parent / "data" / "engine_tiny_scoped.xplane.pb"


@pytest.fixture(scope="module")
def scoped():
    """A trace recorded on a TPU v5e by ``chipbench/layer_report.py --tiny``
    (2 layers, 256 wide, 15 ms traced), with the shapes and scope maps of
    the programs the engine held (``engine_tiny_scoped.json``)."""
    inputs = json.loads(SCOPED.with_suffix("").with_suffix(".json").read_text())
    tr, dims = layers.load(str(SCOPED), run.SPANS + layers.PROGRAM_SPANS + (trace.WINDOW_SPAN,))
    return tr, dims, inputs


def test_scoped_trace_joins_every_run_to_one_program(scoped):
    tr, dims, inputs = scoped
    joined = layers.join(dims, inputs["programs"].values())
    assert set(joined) == set(dims) == {m[2] for m in tr.modules}
    # two prefill programs ran, one module name: the shapes told them apart
    prefills = [p for p in joined if p.startswith("jit_prefill_first_token(")]
    assert len(prefills) == 2 and joined[prefills[0]] != joined[prefills[1]]
    st = layers.scope_time(tr, joined)
    assert not any({"?", layers.AMBIGUOUS} & set(by) for by in st.values())
    decode = collections.Counter()
    for prog, by in st.items():
        if prog.split("(")[0] in inputs["decode_modules"]:
            decode.update(by)
    assert {"attn", "kv_write", "ffn", "norm", "lm_head", "embed", "layers"} <= set(decode)


def test_scoped_trace_scopes_sum_to_module_time_and_idle_tiles_the_window(scoped):
    tr, dims, inputs = scoped
    st = layers.scope_time(tr, layers.join(dims, inputs["programs"].values()))
    old = trace.load(str(SCOPED), run.SPANS + (trace.WINDOW_SPAN,))
    win = trace.window(old, trace.WINDOW_SPAN)
    assert list(win) == inputs["window_ns"]
    red = trace.reduce(old, win)
    sums = collections.Counter()
    for prog, by in st.items():
        sums[prog.split("(")[0]] += sum(by.values())
    assert dict(sums) == pytest.approx(red["module_s"], rel=1e-9)
    idle = layers.idle_by_span(tr, win)
    assert sum(idle.values()) + red["busy_s"] == pytest.approx(red["window_s"], rel=1e-9)
    # the device waited on the program's own spans, which now name the gaps
    assert {"serve.dispatch", "serve.wait", "tuner.begin"} <= set(idle)
    mid = dict(trace.reduce(tr, win)["idle_gaps"])
    assert "serve.dispatch" in mid and "decode_chunk" not in mid
    assert "decode_chunk" in dict(red["idle_gaps"])  # the benchmark's names alone


def test_scoped_trace_metrics(scoped):
    tr, dims, inputs = scoped
    st = layers.scope_time(tr, layers.join(dims, inputs["programs"].values()))
    win = tuple(inputs["window_ns"])
    chunks = layers.span_count(tr, "serve.dispatch", win)
    routed = layers.span_count(tr, "tuner.begin", win)
    assert chunks == 5 and routed == 3
    m = layer_metrics.decode_metrics(
        st, layers.idle_by_span(tr, win), modules=set(inputs["decode_modules"]),
        cfg=inputs["model_config"], batch=inputs["batch"], positions=inputs["positions"],
        peaks=PEAKS, chunks=chunks, routed=routed)
    assert all(v is not None and v > 0 for v in m.values()), m
    assert m["dispatch_idle_ms.decode"] == pytest.approx(
        m["dispatch_idle_ms.decode.dispatch"] + m["dispatch_idle_ms.decode.wait"])
    assert 0 < m["layer_state_share.decode"] < 100
    assert layer_metrics.attn_mfu(st, set(inputs["prefill_modules"]), inputs["model_config"],
                                  inputs["batch"], inputs["seqs"], PEAKS) > 0
