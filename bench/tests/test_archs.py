"""The basic-block ResNet's module gives, bit for bit, what the harness
gave while it wrote that network out itself: the SHA-256 of every weight
leaf for one seed in both configurations, and the reference logits of
four ``paper-cifar32`` images at 64 and 24 bands, with and without the
control (``data/resnet_basic_goldens.json``, recorded on the CPU from
that harness before the network moved into ``archs/resnet_basic.py``)."""
import hashlib
import json
from pathlib import Path

import numpy as np
import jax
import pytest

from bench import data, reference, spec, system

ROOT = Path(__file__).resolve().parents[2]
GOLDEN = json.loads((Path(__file__).parent / "data" /
                     "resnet_basic_goldens.json").read_text())


def _config(name):
    cfg = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    return cfg, spec.arch(ROOT / "bench/archs", cfg["arch"])


@pytest.mark.parametrize("name", ["paper-cifar32", "resnet18-256"])
def test_weights_match_goldens(name):
    cfg, arch = _config(name)
    params, state = system.weights(GOLDEN["seed"], cfg, arch)
    leaves = jax.tree_util.tree_leaves_with_path({"params": params,
                                                  "state": state})
    assert all(np.asarray(x).dtype == np.float32 for _, x in leaves)
    got = {jax.tree_util.keystr(path):
           hashlib.sha256(np.asarray(x).tobytes()).hexdigest()
           for path, x in leaves}
    assert got == GOLDEN["weights_sha256"][name]


@pytest.mark.parametrize("control", [False, True])
@pytest.mark.parametrize("bands", [64, 24])
def test_reference_logits_match_goldens(bands, control):
    cfg, arch = _config("paper-cifar32")
    params, state = system.weights(GOLDEN["seed"], cfg, arch)
    q = np.rint(data.ijg_table(cfg["quality"]))
    imgs = data.images(system.seed_key(GOLDEN["seed"], "images"), n=4,
                       size=32, channels=3, classes=10)
    luma, chroma = (np.asarray(a) for a in data.quantize(imgs, q))
    got = reference.logits(params, state, cfg, arch, luma, chroma, q,
                           bands=bands, control=control, block=4)
    want = GOLDEN["logits"][f"b{bands}/{'control' if control else 'plain'}"]
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, np.asarray(want, np.float32))
