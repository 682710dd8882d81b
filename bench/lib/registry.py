"""Find a cell's configuration, traffic mix and metric readers by name.

``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json`` and
``bench/metrics/<metric>.py`` are found from the names in
``BENCHMARK.json``, and a configuration's graph generator from its
``generator`` key (``bench/generators/<name>.py``); adding a cell, a schema
or a metric adds files and entries and edits none.  A metric reader is a
module with ``read(run) -> float | None``; one reader serves a quantity
split by cell: ``device.idle_share.read`` is read by
``device.idle_share.py`` unless a file of its own full name exists.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent


def load_benchmark(path: Path = REPO / "BENCHMARK.json") -> dict:
    with open(path) as f:
        return json.load(f)


def workload(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def _json(root: Path, sub: str, name: str) -> dict:
    path = root / sub / f"{name}.json"
    with open(path) as f:
        return json.load(f)


def config(name: str, root: Path = BENCH) -> dict:
    return _json(root, "configs", name)


def traffic(name: str, root: Path = BENCH) -> dict:
    return _json(root, "traffic", name)


def _module(path: Path, prefix: str):
    spec = importlib.util.spec_from_file_location(
        f"{prefix}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generator(name: str, root: Path = BENCH) -> Callable:
    return _module(root / "generators" / f"{name}.py", "bench_gen").generate


def metric_reader(name: str, root: Path = BENCH) -> Callable:
    path = root / "metrics" / f"{name}.py"
    if not path.exists() and "." in name:
        path = root / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
    return _module(path, "bench_metric").read


def metrics_for(bm: dict, cell: str, trace: bool) -> List[dict]:
    """The metric entries a run of ``cell`` reports: its end-to-end
    metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``."""
    e2e = [m for m in bm["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bm["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def read_metrics(entries: List[dict], run, root: Path = BENCH
                 ) -> Dict[str, dict]:
    """``{name: {"value", "unit"}}`` for every entry whose reader finds
    something to read."""
    out: Dict[str, dict] = {}
    for m in entries:
        value: Optional[float] = metric_reader(m["name"], root)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
