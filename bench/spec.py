"""What a run is made of, found by name from ``BENCHMARK.json``.

A cell (``workloads`` entry) names a configuration and a traffic mix;
each is a file of its own (``configs/<name>.json``, ``traffic/<mix>.json``
beside this module), and each per-layer metric is a reader of its own
(``metrics/<name>.py``, one ``read(run)`` function).  Adding a cell,
a mix or a metric is adding files and entries; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


def load_benchmark(root: Path) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def cell(root: Path, workload: str) -> dict:
    """The workload entry with its configuration, traffic and metrics
    resolved: ``{"workload", "config", "traffic", "end_to_end",
    "per_layer"}``.  Unknown names raise ``KeyError``."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[w["config"]]
    with open(root / entry["file"]) as f:
        config = json.load(f)
    traffic_dir = root / Path(entry["file"]).parent.parent / "traffic"
    with open(traffic_dir / f"{w['traffic']}.json") as f:
        traffic = json.load(f)

    def applies(m: dict) -> bool:
        return "workloads" not in m or workload in m["workloads"]

    return {"workload": w, "config": config, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)],
            "metrics_dir": traffic_dir.parent / "metrics"}


def reader(metrics_dir: Path, name: str):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = Path(metrics_dir) / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind`` (``peaks.json``);
    a kind not in the table is an error, never a default."""
    with open(HERE / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]
