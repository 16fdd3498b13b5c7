"""Finds what a cell needs by name: its workload file, configuration,
traffic mix, engine, reference block and metric readers, and the metric
entries ``BENCHMARK.json`` gives it.  A new cell, configuration, mix,
engine or metric is a new file here; nothing in this module names one."""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def _json(kind: str, name: str) -> dict:
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def names(kind: str) -> list:
    """Every name of ``kind`` (``configs``, ``traffic``, ``workloads``,
    ``metrics``, ``engines``, ``reference``) that has a file."""
    suffix = ".py" if kind in ("metrics", "engines", "reference") else ".json"
    return sorted(p.name[: -len(suffix)] for p in (HERE / kind).glob(f"*{suffix}")
                  if not p.name.startswith("_"))


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell(name: str) -> dict:
    """The cell's workload file with its configuration and traffic mix."""
    entry = {w["name"]: w for w in benchmark()["workloads"]}.get(name)
    if entry is None:
        raise KeyError(f"{name!r} is not a workload of BENCHMARK.json")
    spec = _json("workloads", name)
    if (spec["config"], spec["traffic"]) != (entry["config"], entry["traffic"]):
        raise ValueError(f"{name}: workload file and BENCHMARK.json disagree")
    return {**spec, "name": name, "chips": int(entry["chips"]),
            "config_file": _json("configs", spec["config"]),
            "traffic_spec": _json("traffic", spec["traffic"])}


def model_config(config_file: dict):
    """The program's ``ModelConfig`` as the configuration file states it."""
    from repro.models import ModelConfig

    fields = {k: tuple(v) if isinstance(v, list) else v
              for k, v in config_file["model_config"].items()}
    known = {f.name for f in dataclasses.fields(ModelConfig)}
    unknown = sorted(set(fields) - known)
    if unknown:
        raise ValueError(f"model_config keys the program does not know: {unknown}")
    return ModelConfig(**fields)


def engine(name: str):
    return importlib.import_module(f"chipbench.engines.{name}")


def reference(name: str):
    return importlib.import_module(f"chipbench.reference.{name}")


def reader(metric: str):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = HERE / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(f"chipbench.metrics.{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(cell_name: str, traced: bool) -> list:
    """The metric entries of ``BENCHMARK.json`` a run of this cell reports:
    the end-to-end ones untraced, the per-layer ones traced."""
    entries = benchmark()["per_layer" if traced else "end_to_end"]
    return [m for m in entries if cell_name in m.get("workloads", [cell_name])]
