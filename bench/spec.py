"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell ``<config>.<traffic>`` names a configuration file
(``configs[].file``), a traffic file ``bench/traffic/<traffic>.json``,
its end-to-end metrics (``bench/end_to_end/<metric>.py``) and its
per-layer metrics (``bench/metrics/<metric>.py``).  Adding a
configuration, a traffic mix or a metric is adding its file and its
entry; no file here changes.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import List

ROOT = Path(__file__).resolve().parents[1]


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    names = ", ".join(w["name"] for w in bench["workloads"])
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json ({names})")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            with open(root / c["file"]) as f:
                return json.load(f)
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, root: Path = ROOT) -> dict:
    with open(root / "bench" / "traffic" / f"{name}.json") as f:
        return json.load(f)


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def end_to_end(bench: dict, workload: str) -> List[dict]:
    return [m for m in bench["end_to_end"] if applies(m, workload)]


def per_layer(bench: dict, workload: str) -> List[dict]:
    return [m for m in bench["per_layer"] if applies(m, workload)]


def reader(kind: str, name: str, root: Path = ROOT) -> ModuleType:
    """The module ``bench/<kind>/<name>.py``; it defines ``read``."""
    path = root / "bench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
