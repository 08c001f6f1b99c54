"""The benchmark's basic-block programs are the ones they were before
bottleneck blocks, the pointwise route and the max-pool stem came in.

For the specs of ``resnet18-256`` and ``paper-cifar32`` (built by
``bench/archs/resnet_basic.py`` from their configuration files), the
served program — the top tier's compiled schedule captured as a grid cell
captures it, for the off-TPU ``gemm`` executor and for the schedule's own
paths — is lowered on the CPU and its StableHLO, without locations and
with function names numbered in order of appearance, hashed.  The
digests were recorded from the tree before that change.  ``resnet18-256``
is lowered at 64 px (its spec fixes widths and depth, not the image; the
operator routes depend on the channels alone), batch 2, so nothing of the
published image size is traced.  A change that means to alter these
programs updates the digests, and says so.
"""
import hashlib
import importlib.util
import json
import re
from pathlib import Path

import jax
import pytest

from repro.core import dispatch as D
from repro.core import plan as PL

ROOT = Path(__file__).resolve().parents[1]

DIGESTS = {
    ("paper-cifar32", "gemm"):
        "29f0b43609daa53d06aa44c97d37cd651cd0a7c185aecfed72f53283dc00c7fb",
    ("paper-cifar32", None):
        "e27b69049ebdc1c332831106c7fdf159f99c283cdbc8d332ebd3468e958ef354",
    ("resnet18-256", "gemm"):
        "e6b68f2903e10aa17c4725802d457d4731ed773a4fff3a2d9db55b07bdb0e033",
    ("resnet18-256", None):
        "262c8eaf2469ab11449ec100354eafa06396ab1cd9229e8cfd7a40674c42b6ed",
}
IMAGE = {"paper-cifar32": 32, "resnet18-256": 64}


def _normalized(text: str) -> str:
    names: dict[str, str] = {}
    return re.sub(r"@[A-Za-z_][\w.$-]*",
                  lambda m: names.setdefault(m.group(0), f"@f{len(names)}"),
                  text)


@pytest.fixture(scope="module")
def schedules():
    spec = importlib.util.spec_from_file_location(
        "_arch_resnet_basic", ROOT / "bench/archs/resnet_basic.py")
    arch = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(arch)
    out = {}
    for name, image in IMAGE.items():
        cfg = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
        params, state = arch.weights(jax.random.PRNGKey(0), cfg)
        plan = PL.build_plan(params, state, arch.program_spec(cfg),
                             dispatch=D.DispatchConfig(bands=cfg["bands"]))
        out[name] = (PL.compile_plan(plan, image_size=image), cfg, image)
    return out


@pytest.mark.parametrize("name,executor", sorted(
    DIGESTS, key=lambda k: (k[0], str(k[1]))),
    ids=lambda v: str(v))
def test_served_program_unchanged(schedules, name, executor):
    cp, cfg, image = schedules[name]
    assert cp.meta["routes"]["pointwise"] == 0
    nb = image // 8
    fn = PL.capture_compiled(cp, (2, nb, nb, cfg["in_channels"], 64),
                             executor=executor)
    text = _normalized(fn.lower().as_text(debug_info=False))
    assert hashlib.sha256(text.encode()).hexdigest() \
        == DIGESTS[(name, executor)]
