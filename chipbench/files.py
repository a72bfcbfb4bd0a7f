"""Find the benchmark's data by name: the cell in ``BENCHMARK.json``, its
configuration and traffic files, and the per-layer metric readers."""
from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Any, Dict, List

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def load_json(path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark(root=ROOT) -> Dict[str, Any]:
    return load_json(pathlib.Path(root) / "BENCHMARK.json")


def cell(name: str, bench: Dict[str, Any]) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{sorted(w['name'] for w in bench['workloads'])}")


def config(name: str, base=HERE) -> Dict[str, Any]:
    return load_json(pathlib.Path(base) / "configs" / f"{name}.json")


def traffic(name: str, base=HERE) -> Dict[str, Any]:
    return load_json(pathlib.Path(base) / "traffic" / f"{name}.json")


def metric_reader(name: str, base=HERE):
    """The module ``metrics/<name>.py``: NAME, UNIT, LAYER, MOVES, SOURCE
    and ``read(ctx) -> float | None``."""
    path = pathlib.Path(base) / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if mod.NAME != name:
        raise ValueError(f"{path} declares NAME {mod.NAME!r}, not {name!r}")
    return mod


def cell_metrics(cell_name: str, kind: str, bench: Dict[str, Any]
                 ) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` entries that this cell reports:
    those without a ``workloads`` key, and those that list the cell."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]
