"""Trace reduction: interval arithmetic, and a small trace recorded on a
TPU v5e by a traced run of ``paper-cifar32.coef.closed``."""
from pathlib import Path

import pytest

from bench import xplane

TRACE = Path(__file__).parent / "data" / "cifar_coef.xplane.pb"


def test_union_and_instruction_names():
    assert xplane.union([(3, 4), (0, 1), (0.5, 2), (4, 5)]) == [(0, 2),
                                                                 (3, 5)]
    assert xplane.instruction(
        "%asm_relu_pallas.16 = f32[2097152,64]{1,0} custom-call(%a)"
    ) == "asm_relu_pallas.16"
    assert xplane.instruction("fusion.3") == "fusion.3"


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    import gzip
    import shutil

    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(str(TRACE) + ".gz") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return xplane.reduce(str(path))


def test_reduce_recorded_trace(reduced):
    """A 0.2 s window of ``paper-cifar32.coef.closed``, traced on one TPU
    v5e: what the reduction reads there, as that run reported it."""
    assert reduced["window_s"] == pytest.approx(0.19968121500000002)
    assert reduced["busy_s"] == pytest.approx(0.153838204)
    assert reduced["devices"] == 1
    assert len(reduced["modules"]["jit_inner"]) == 11
    assert reduced["ops"]["jpeg_conv_pallas.1"][0] == 12
    ops = reduced["breakdown"]["device_ops"]
    assert ops[0][0] == "jpeg_conv_pallas.1"
    assert len(ops) == xplane.TOP
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    gaps = reduced["breakdown"]["idle_gaps"]
    assert len(gaps) == xplane.TOP
    assert all(0 < s < reduced["window_s"] for _, s in gaps)


def test_metric_readers_on_recorded_trace(reduced):
    """The device-trace readers on that trace: shares strictly between 0
    and 100, the ASM ReLU bound by compute since the compiled program
    keeps its rows in VMEM."""
    from bench import flops, spec

    root = Path(__file__).resolve().parents[2]
    hlo = (Path(__file__).parent / "data" /
           "cifar_coef_kernels.hlo.txt").read_text()

    class View:
        trace = reduced
        kernels = flops.custom_calls(hlo)
        peak = spec.peaks("TPU v5 lite")
        notes = []

        def note(self, text):
            self.notes.append(text)

    view = View()
    metrics = spec.cell(root, "paper-cifar32.coef.closed")["metrics_dir"]
    roofline = spec.reader(metrics, "asm_relu_roofline")(view)
    assert roofline == pytest.approx(18.284819219446895)
    assert "compute" in view.notes[0]
    idle = spec.reader(metrics, "device_idle_share")(view)
    assert idle == pytest.approx(100 * (1 - 0.153838204 / 0.19968121500000002))
