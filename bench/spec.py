"""What a run is made of, found by name from ``BENCHMARK.json``.

A cell (``workloads`` entry) names a configuration and a traffic mix;
each is a file of its own (``configs/<name>.json``, ``traffic/<mix>.json``
beside this module).  A configuration names its architecture
(``"arch"``), a module of its own (``archs/<arch>.py``, the four
functions of ``archs/__init__.py``'s contract), and each per-layer
metric is a reader of its own (``metrics/<name>.py``, one ``read(run)``
function).  Adding a cell, a mix, a metric or an architecture is adding
files and entries; nothing here changes.
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
    """The workload entry with its configuration, architecture module,
    traffic and metrics resolved: ``{"workload", "config", "arch",
    "traffic", "end_to_end", "per_layer", "metrics_dir"}``.  Unknown
    names, and a configuration that names no architecture, raise
    ``KeyError``."""
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
    bench_dir = root / Path(entry["file"]).parent.parent
    archs_dir = bench_dir / "archs"
    if "arch" not in config:
        raise KeyError(f"configuration {w['config']!r} names no 'arch' "
                       f"(have {_architectures(archs_dir)})")
    with open(bench_dir / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)

    def applies(m: dict) -> bool:
        return "workloads" not in m or workload in m["workloads"]

    return {"workload": w, "config": config,
            "arch": arch(archs_dir, config["arch"]), "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)],
            "metrics_dir": bench_dir / "metrics"}


def _architectures(archs_dir: Path) -> list[str]:
    """Names of the architecture modules in ``archs_dir``."""
    return sorted(p.stem for p in Path(archs_dir).glob("*.py")
                  if not p.stem.startswith("_"))


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def arch(archs_dir: Path, name: str):
    """The architecture module ``archs/<name>.py``; an unknown name
    raises ``KeyError`` with the names there are."""
    if name not in _architectures(archs_dir):
        raise KeyError(f"no architecture {name!r} in {archs_dir} "
                       f"(have {_architectures(archs_dir)})")
    return _load(Path(archs_dir) / f"{name}.py", f"bench_arch_{name}")


def reader(metrics_dir: Path, name: str):
    """The ``read`` function of ``metrics/<name>.py``."""
    return _load(Path(metrics_dir) / f"{name}.py",
                 f"bench_metric_{name}").read


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind`` (``peaks.json``);
    a kind not in the table is an error, never a default."""
    with open(HERE / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]
