"""Find everything by name: cells, configurations, traffic, drivers, metrics, readers.

Adding a cell, a configuration, an architecture family, a mix, a driver, a
reader or a per-layer metric is adding files plus entries in
``BENCHMARK.json``; nothing here is edited for it.
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def manifest(root: str = ROOT) -> Dict[str, Any]:
    return _json(os.path.join(root, "BENCHMARK.json"))


def cell(name: str, root: str = ROOT) -> Dict[str, Any]:
    for w in manifest(root)["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_config(name: str, root: str = ROOT) -> Dict[str, Any]:
    """The configuration's file, found through ``configs[].file``."""
    for c in manifest(root)["configs"]:
        if c["name"] == name:
            arch = _json(os.path.join(root, c["file"]))
            arch["name"] = name
            return arch
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def load_traffic(name: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    t = _json(os.path.join(bench_dir, "traffic", f"{name}.json"))
    t["name"] = name
    return t


def driver(kind: str) -> Callable[..., Any]:
    return importlib.import_module(f"harness.drivers.{kind}").run


def reader(name: str) -> Callable[..., Any]:
    return importlib.import_module(f"readers.{name}").read


def metrics_for(cell_name: str, trace: bool, root: str = ROOT) -> List[Dict[str, Any]]:
    """The manifest's metrics that this cell reports in this kind of run."""
    man = manifest(root)
    out = []
    for m in man["per_layer" if trace else "end_to_end"]:
        if "workloads" not in m or cell_name in m["workloads"]:
            out.append(m)
    return out


def layer_metric_spec(name: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    """``{"reader": ..., "args": ...}``; unit, layer and ``moves`` are BENCHMARK.json's."""
    return _json(os.path.join(bench_dir, "layer_metrics", f"{name}.json"))


def list_all(root: str = ROOT) -> Dict[str, List[str]]:
    """Names the harness can see; what the add-a-file test looks at."""
    man = manifest(root)
    bdir = os.path.join(root, man["paths"][0])

    def stems(sub: str, ext: str) -> List[str]:
        d = os.path.join(bdir, sub)
        return sorted(
            f[: -len(ext)] for f in os.listdir(d)
            if f.endswith(ext) and not f.startswith("_")
        )

    return {
        "workloads": [w["name"] for w in man["workloads"]],
        "configs": [c["name"] for c in man["configs"]],
        "traffic": stems("traffic", ".json"),
        "drivers": stems(os.path.join("harness", "drivers"), ".py"),
        "families": stems(os.path.join("harness", "families"), ".py"),
        "references": sorted(set(stems("references", ".py")) - {"common"}),
        "layer_metrics": stems("layer_metrics", ".json"),
        "readers": stems("readers", ".py"),
    }
