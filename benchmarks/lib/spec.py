"""Finding a cell's files by name: BENCHMARK.json -> configuration, traffic
mix and per-layer metric files. Nothing here imports jax or the program."""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                   f"{[c['name'] for c in bench['workloads']]}")


def load_config(bench: dict, name: str) -> dict:
    for entry in bench["configs"]:
        if entry["name"] == name:
            cfg = load_json(os.path.join(ROOT, entry["file"]))
            cfg["_name"] = name
            return cfg
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_traffic(name: str) -> dict:
    traffic = load_json(os.path.join(BENCH_DIR, "traffic", f"{name}.json"))
    traffic["_name"] = name
    return traffic


def metrics_of(bench: dict, group: str, cell: str) -> List[dict]:
    """The metrics of ``group`` (``end_to_end`` or ``per_layer``) that the
    cell reports: those with no ``workloads`` key, and those that list it."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def load_layer_metric(name: str) -> dict:
    return load_json(os.path.join(BENCH_DIR, "layer_metrics", f"{name}.json"))


def layer_specs(bundle: dict) -> Dict[str, dict]:
    """The reader file of every per-layer metric the cell reports."""
    return {m["name"]: load_layer_metric(m["name"])
            for m in bundle["per_layer"]}


def resolve(dotted: str) -> Any:
    """``"package.module:attr"`` -> the object."""
    module, _, attr = dotted.partition(":")
    obj = importlib.import_module(module)
    for part in attr.split("."):
        obj = getattr(obj, part)
    return obj


def program_config(cfg: dict) -> Any:
    """The program's own configuration object, built from the file's sizes
    by the field map the file carries (``program.fields``: program field ->
    key of this file). Runs in the process that will use the object."""
    prog = cfg["program"]
    cls = resolve(prog["config_class"])
    obj = cls(**{field: cfg[key] for field, key in prog["fields"].items()})
    for attr, key in prog.get("check", {}).items():
        if getattr(obj, attr) != cfg[key]:
            raise ValueError(
                f"{cfg.get('_name')}: the program's {attr}="
                f"{getattr(obj, attr)!r} is not the file's {key}={cfg[key]!r}")
    return obj


def apply_rehearsal(cfg: dict, traffic: dict) -> None:
    """Shrink a configuration and a traffic mix in place to the tiny sizes
    of ``rehearsal.json``: for debugging the harness on the CPU, never a
    measurement."""
    tiny = load_json(os.path.join(BENCH_DIR, "rehearsal.json"))
    cfg.update(tiny["config"])
    for key, val in tiny.get("deployment", {}).items():
        if "deployment" in cfg:
            cfg["deployment"][key] = val
    traffic.update(tiny["traffic"].get(traffic["kind"], {}))


def cell_bundle(workload: str, rehearsal: bool = False) -> Dict[str, Any]:
    bench = load_benchmark()
    cell = find_cell(bench, workload)
    cfg = load_config(bench, cell["config"])
    traffic = load_traffic(cell["traffic"])
    if rehearsal:
        apply_rehearsal(cfg, traffic)
    return {"bench": bench, "cell": cell, "config": cfg, "traffic": traffic,
            "end_to_end": metrics_of(bench, "end_to_end", workload),
            "per_layer": metrics_of(bench, "per_layer", workload)}
