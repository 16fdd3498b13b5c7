"""The closed-batch engine and a whole run of ``chipbench/run.py`` at tiny
size on the CPU: the device check skipped, everything after it driven as
on the chip (set-up, window, sample, reference, checks)."""
import time

import numpy as np
import pytest
from bench_helpers import PEAKS, tiny_cell

from chipbench import catalog, run


@pytest.fixture(scope="module", params=["qwen2_7b"])
def tiny_run(request):
    cell = tiny_cell(request.param, 1.0)
    return cell, run.measure(cell, 2**40 + 3, 1.0, False, t_start=time.perf_counter(), peaks=PEAKS)


def test_run_is_correct_and_compiles_nothing_in_the_window(tiny_run):
    cell, out = tiny_run
    res = out["result"]
    assert res["correct"] is True
    assert res["checks"]["window_compiles"]["value"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert out["gaps"]["requests"] >= cell["check"]["sample"]


def test_token_and_chunk_accounting(tiny_run):
    cell, out = tiny_run
    rec, tr = out["run"], cell["traffic_spec"]
    finished = [j for j in rec.jobs if j.finished]
    # the window closes on a job boundary: it holds whole jobs only
    assert len(finished) == len(rec.jobs) >= 2
    assert rec.window[1] == pytest.approx(max(j.chunks[-1].t1 for j in rec.jobs), abs=1e-3)
    for j in finished:
        assert j.served.shape == (tr["batch"], 1 + tr["gen"])
        assert sum(c.k for c in j.chunks) == tr["gen"]
        P = j.prompts.shape[1]
        assert [c.pos for c in j.chunks] == list(P + np.cumsum([0] + [c.k for c in j.chunks[:-1]]))
        assert all(c.t0 <= c.t1 for c in j.chunks) and j.t_start <= j.t_first <= j.chunks[0].t0
    # both prompt lengths of the mix were served, in seed order
    assert {j.prompts.shape[1] for j in finished} == {8, 16}
    s = out["router_stats"]
    routed = sum(1 for j in rec.jobs for c in j.chunks if c.k in (1, 2, 4, 8)
                 and j.emitted - sum(x.k for x in j.chunks[j.chunks.index(c):]) <= tr["gen"] - 8)
    assert s["explores"] + s["exploits"] == routed
    assert s["explores"] == sum(c.explore for j in rec.jobs for c in j.chunks)
    assert s["inband_builds"] == 0 and s["candidate_failures"] == 0


def test_metric_readers_on_the_run(tiny_run):
    cell, out = tiny_run
    rec = out["run"]
    t0, t1 = rec.window
    tokens = catalog.reader("serve_tok_s")(rec) * (t1 - t0)
    expect = sum(cell["traffic_spec"]["batch"] * (1 + sum(c.k for c in j.chunks if c.t1 <= t1))
                 for j in rec.jobs if j.t_first <= t1)
    assert tokens == pytest.approx(expect)
    per_tok = [(c.t1 - c.t0) / c.k * 1e3 for j in rec.jobs for c in j.chunks if c.t1 <= t1]
    tpot = catalog.reader("tpot_p95_ms")(rec)
    assert min(per_tok) <= tpot <= max(per_tok)
    ttft = catalog.reader("ttft_p90_ms")(rec)
    assert ttft == pytest.approx(np.quantile([(j.t_first - j.t_start) * 1e3 for j in rec.jobs
                                              if j.t_first <= t1], 0.9, method="inverted_cdf"))
    assert 0 <= catalog.reader("explore_share.decode")(rec) <= 25.0 + 1e-9
    assert catalog.reader("setup_s")(rec) > 0
    # untraced: the device readers find nothing to read and report nothing
    for name in ("mfu.decode", "mfu.prefill", "decode_step_roofline", "idle_share.decode"):
        assert catalog.reader(name)(rec) is None


def test_seeded_traffic_is_a_fixed_mix_in_seed_order():
    from chipbench.traffic import Traffic

    spec = catalog.cell("qwen2_7b.prefill")["traffic_spec"]
    a, b = Traffic(spec, 2**35 + 1, 100), Traffic(spec, 7, 100)
    la = [a.prompt_len(i) for i in range(40)]
    lb = [b.prompt_len(i) for i in range(40)]
    assert la != lb
    for i in range(0, 40, 10):  # each block of ten: 5 x 1k, 3 x 2k, 2 x 4k
        assert sorted(la[i:i + 10]) == sorted(lb[i:i + 10]) == [1024] * 5 + [2048] * 3 + [4096] * 2
    np.testing.assert_array_equal(a.prompts(3), Traffic(spec, 2**35 + 1, 100).prompts(3))
    assert a.prompts(3).shape == (1, la[3])
