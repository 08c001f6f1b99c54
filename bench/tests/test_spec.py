"""The harness finds everything by name, and the peaks table has no
default."""
import json
import shutil
import time
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
        assert cell["arch"].__name__.endswith(cell["config"]["arch"])
        assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}
        assert cell["per_layer"], w["name"]
        for m in cell["per_layer"]:
            assert callable(spec.reader(cell["metrics_dir"], m["name"]))


#: appended to a copy of ``resnet_basic.py``: records each call of the
#: architecture contract, so a test sees which module served a run
RECORDER = """

CALLS = []


def _recorded(fn):
    def call(*args, **kwargs):
        CALLS.append(fn.__name__)
        return fn(*args, **kwargs)
    return call


program_spec, weights, forward, model_flops = map(
    _recorded, (program_spec, weights, forward, model_flops))
"""


def _bench_copy(tmp_path) -> dict:
    """``bench/`` (its tests left out) copied under ``tmp_path``; returns
    its ``BENCHMARK.json`` entries, to be extended and written back."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _add_config(tmp_path, bench, name, **changes) -> None:
    """A ``paper-cifar32`` copy named ``name``, with ``changes`` (a value
    of ``None`` drops the key), and a cell ``<name>.new`` of it."""
    cfg = json.loads((ROOT / "bench/configs/paper-cifar32.json").read_text())
    cfg["name"] = name
    cfg.update(changes)
    cfg = {k: v for k, v in cfg.items() if v is not None}
    (tmp_path / f"bench/configs/{name}.json").write_text(json.dumps(cfg))
    bench["configs"].append({"name": name, "source": "x",
                             "file": f"bench/configs/{name}.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": f"{name}.new", "config": name,
                               "traffic": "new-mix", "chips": 1,
                               "why": "x"})


def test_cell_added_as_new_files_only_is_found(tmp_path, monkeypatch):
    """A later PR adds an architecture, a configuration, a traffic mix
    and a per-layer metric as new files plus new BENCHMARK.json entries:
    the harness finds each by name without an edit to any existing file,
    and a whole run of the new cell on the CPU takes its weights,
    reference and FLOP count from the new architecture's file."""
    from bench import harness

    bench = _bench_copy(tmp_path)
    new_arch = tmp_path / "bench/archs/new_arch.py"
    new_arch.write_text((ROOT / "bench/archs/resnet_basic.py").read_text()
                        + RECORDER)
    _add_config(tmp_path, bench, "new-config", arch="new_arch")
    mix = json.loads((ROOT / "bench/traffic/coef-closed-c128-b64.json")
                     .read_text())
    mix["clients"] = 7
    (tmp_path / "bench/traffic/new-mix.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench["per_layer"].append({"name": "new_metric", "unit": "ms",
                               "better": "lower", "source": "program_span",
                               "layer": "scheduler", "moves": "p95_ms",
                               "workloads": ["new-config.new"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.cell(tmp_path, "new-config.new")
    assert cell["config"]["name"] == "new-config"
    assert Path(cell["arch"].__file__) == new_arch
    assert cell["traffic"]["clients"] == 7
    names = [m["name"] for m in cell["per_layer"]]
    assert "new_metric" in names
    assert spec.reader(cell["metrics_dir"], "new_metric")(None) == 42.0
    # metrics scoped to other cells stay out
    assert "dispatch_ms" not in names

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    out = harness.run(tmp_path, "new-config.new", 2 ** 32 + 41, 1.0, False,
                      t_process=time.monotonic(), require_chips=False,
                      cell=cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert {"program_spec", "weights", "forward"} <= set(cell["arch"].CALLS)
    # step_mfu's FLOPs come from the cell's architecture: 4 images in
    # 0.5 s of device time on a 1 TFLOP/s chip
    view = harness.RunView(
        window=(0.0, 1.0), spans=[("device", "device-dispatch", 0.0, 1.0,
                                   {"n": 4})],
        trace={"modules": {"m": [(0.25, 0.5)]}}, trace_offset=0.0,
        kernels=[], modules={"m"}, config=cell["config"],
        arch=cell["arch"], peak={"bf16_flops_per_s": 1e12})
    mfu = spec.reader(cell["metrics_dir"], "step_mfu")(view)
    assert "model_flops" in cell["arch"].CALLS
    assert mfu == pytest.approx(100 * 25_003_264 * 4 / 0.5 / 1e12)


@pytest.mark.parametrize("arch", [None, "resnet_basik"])
def test_config_without_a_known_arch_raises(tmp_path, arch):
    """No default architecture: a configuration that names none, or one
    that is not in ``archs/``, raises with the names there are."""
    bench = _bench_copy(tmp_path)
    (tmp_path / "bench/archs/new_arch.py").write_text(
        (ROOT / "bench/archs/resnet_basic.py").read_text())
    _add_config(tmp_path, bench, "bad-config", arch=arch)
    (tmp_path / "bench/traffic/new-mix.json").write_text(
        (ROOT / "bench/traffic/coef-closed-c128-b64.json").read_text())
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(KeyError, match=r"\['new_arch', 'resnet_basic'\]"):
        spec.cell(tmp_path, "bad-config.new")
