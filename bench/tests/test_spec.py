"""The harness finds everything by name, and the peaks table has no
default."""
import json
import shutil
from pathlib import Path

import pytest

from bench import spec

ROOT = Path(__file__).resolve().parents[2]


def test_peaks_unknown_device_kind_raises():
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks for device kind"):
        spec.peaks("TPU v9 imaginary")


def test_every_cell_resolves():
    bench = spec.load_benchmark(ROOT)
    for w in bench["workloads"]:
        cell = spec.cell(ROOT, w["name"])
        assert cell["config"]["name"] == w["config"]
        assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}
        assert cell["per_layer"], w["name"]
        for m in cell["per_layer"]:
            assert callable(spec.reader(cell["metrics_dir"], m["name"]))


def test_cell_added_as_new_files_only_is_found(tmp_path):
    """A later PR adds a configuration, a traffic mix and a per-layer
    metric as new files plus new BENCHMARK.json entries: the harness
    finds each by name without an edit to any existing file."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/paper-cifar32.json").read_text())
    cfg["name"] = "new-config"
    (tmp_path / "bench/configs/new-config.json").write_text(json.dumps(cfg))
    mix = json.loads((ROOT / "bench/traffic/coef-closed-c128-b64.json")
                     .read_text())
    mix["clients"] = 7
    (tmp_path / "bench/traffic/new-mix.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench["configs"].append({"name": "new-config", "source": "x",
                             "file": "bench/configs/new-config.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new-config.new", "config":
                               "new-config", "traffic": "new-mix",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "new_metric", "unit": "ms",
                               "better": "lower", "source": "program_span",
                               "layer": "scheduler", "moves": "p95_ms",
                               "workloads": ["new-config.new"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.cell(tmp_path, "new-config.new")
    assert cell["config"]["name"] == "new-config"
    assert cell["traffic"]["clients"] == 7
    names = [m["name"] for m in cell["per_layer"]]
    assert "new_metric" in names
    assert spec.reader(cell["metrics_dir"], "new_metric")(None) == 42.0
    # metrics scoped to other cells stay out
    assert "dispatch_ms" not in names
