"""Every configuration, traffic mix, cell, engine, reference and metric of
the benchmark loads by name, and ``BENCHMARK.json`` agrees with the files
and keeps to the shape its readers expect."""
import json
import re

import pytest

from chipbench import catalog, peaks

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
BENCH = catalog.benchmark()

#: published config.json key -> the program's ModelConfig field
WIDTHS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
          "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
          "num_hidden_layers": "n_layers", "vocab_size": "vocab_size",
          "rope_theta": "rope_theta", "head_size": "d_head"}


def test_every_cell_loads_with_its_config_traffic_engine_and_reference():
    assert sorted(w["name"] for w in BENCH["workloads"]) == catalog.names("workloads")
    for w in BENCH["workloads"]:
        cell = catalog.cell(w["name"])
        cfg = catalog.model_config(cell["config_file"])
        assert cfg.param_dtype == "bfloat16"
        assert catalog.engine(cell["engine"]).Engine
        assert catalog.reference(cell["config_file"]["reference"]).apply
        assert cell["traffic_spec"]["loop"] == "closed"
        limits = cell["check"]["limits"]
        assert limits and set(limits) == {"max_logit_gap"}
        assert all(v > 0 for v in limits.values()) and cell["check"]["sample"] >= 1


def test_every_config_states_its_widths_as_run():
    files = {c["name"]: c for c in BENCH["configs"]}
    assert sorted(files) == catalog.names("configs")
    for name, entry in files.items():
        data = json.loads((catalog.ROOT / entry["file"]).read_text())
        assert data["source"] == entry["source"] and data["reduced"] == entry["reduced"]
        mc = data["model_config"]
        for key, field in WIDTHS.items():
            if key in data:
                assert mc[field] == data[key], (name, key)
        for key in data["reduced"]:
            assert data["published"][key] != data[key]
        assert set(data["published"]) == set(data["reduced"])


def test_every_metric_has_a_reader_and_a_kept_shape():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert sorted(m["name"] for m in metrics) == catalog.names("metrics")
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in metrics:
        assert NAME.match(m["name"]) and callable(catalog.reader(m["name"]))
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        # a per-layer metric lists only cells that report what it moves
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for cell in cells:
        assert "setup_s" in {m["name"] for m in catalog.metrics_for(cell, False)}
        assert len(catalog.metrics_for(cell, False)) >= 2 and catalog.metrics_for(cell, True)


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"][1] == "chipbench/run.py"
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200 and w["chips"] == 1
    assert len({(w["config"], w["traffic"]) for w in BENCH["workloads"]}) == len(BENCH["workloads"])
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
