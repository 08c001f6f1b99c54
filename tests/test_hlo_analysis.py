"""The roofline extractor: trip-count-aware HLO costing."""
import os
import subprocess
import sys

import pytest

from repro.launch.hlo_analysis import HloCost, _split_computations, analyze_hlo

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SAMPLE = """\
HloModule test, entry_computation_layout={()->f32[4,4]{1,0}}

%body (p: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
  %p = (s32[], f32[4,4]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[4,4]{1,0} get-tuple-element(%p), index=1
  %y = f32[4,4]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[4,4]) tuple(%i2, %y)
}

%cond (p2: (s32[], f32[4,4])) -> pred[] {
  %p2 = (s32[], f32[4,4]) parameter(0)
  %i3 = s32[] get-tuple-element(%p2), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%i3, %n), direction=LT
}

ENTRY %main () -> f32[4,4] {
  %c = f32[4,4]{1,0} constant(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[4,4]) tuple(%zero, %c)
  %w = (s32[], f32[4,4]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %out = f32[4,4]{1,0} get-tuple-element(%w), index=1
}
"""


def test_while_trip_multiplication():
    cost = analyze_hlo(SAMPLE, 1)
    # 5 iterations × (2·4·4·4 dot flops + 16-ish elementwise)
    assert cost.flops >= 5 * 2 * 4 * 4 * 4
    assert cost.flops < 5 * 2 * 4 * 4 * 4 + 5 * 64
    assert not cost.warnings


def test_comment_stripping():
    """Tuple types embed /*index=N*/ comments; the parser must survive."""
    txt = SAMPLE.replace("(s32[], f32[4,4]) tuple",
                         "(s32[], /*index=1*/f32[4,4]) tuple")
    comps = _split_computations(txt)
    assert "body" in comps or "%body" in [k for k in comps]
    cost = analyze_hlo(txt, 1)
    assert cost.flops >= 5 * 2 * 64


def test_collective_accounting():
    txt = """\
ENTRY %main (p: f32[16,16]) -> f32[16,16] {
  %p = f32[16,16]{1,0} parameter(0)
  %ag = f32[16,64]{1,0} all-gather(%p), channel_id=1, replica_groups=[2,4]<=[8], dimensions={1}
  %sl = f32[16,16]{1,0} slice(%ag), slice={[0:16],[0:16]}
  ROOT %ar = f32[16,16]{1,0} all-reduce(%sl), channel_id=2, replica_groups=[2,4]<=[8], to_apply=%add
}
"""
    cost = analyze_hlo(txt, 8)
    kinds = {c.kind for c in cost.collectives}
    assert kinds == {"all-gather", "all-reduce"}
    ag = next(c for c in cost.collectives if c.kind == "all-gather")
    assert ag.bytes == 16 * 64 * 4
    assert ag.group_size == 4
    ar = next(c for c in cost.collectives if c.kind == "all-reduce")
    assert ar.bytes == 2 * 16 * 16 * 4  # ring convention: 2× payload
    assert cost.collective_bytes_by_group_size()[4] > 0


def test_json_roundtrip():
    cost = analyze_hlo(SAMPLE, 1)
    j = cost.to_json()
    assert j["flops"] == cost.flops
    assert "collective_bytes" in j
    assert "per_computation" not in j  # only emitted when requested


def test_per_computation_buckets_sum_to_totals():
    cost = analyze_hlo(SAMPLE, 1, per_computation=True)
    per = cost.per_computation
    assert per  # named sub-computation -> HloCost
    for field in ("flops", "bytes", "transcendentals"):
        assert sum(getattr(c, field) for c in per.values()) \
            == pytest.approx(getattr(cost, field)), field
    # the while body's dot FLOPs land (trip-multiplied) in its own bucket
    body = next(v for k, v in per.items() if "body" in k)
    assert body.flops >= 5 * 2 * 4 * 4 * 4


def test_per_computation_collectives_and_json():
    txt = """\
ENTRY %main (p: f32[16,16]) -> f32[16,16] {
  %p = f32[16,16]{1,0} parameter(0)
  ROOT %ar = f32[16,16]{1,0} all-reduce(%p), channel_id=2, replica_groups=[2,4]<=[8], to_apply=%add
}
"""
    cost = analyze_hlo(txt, 8, per_computation=True)
    total = sum(c.collective_bytes for c in cost.per_computation.values())
    assert total == pytest.approx(cost.collective_bytes)
    j = cost.to_json()
    assert set(j["per_computation"]) == set(cost.per_computation)
    ent = next(iter(j["per_computation"].values()))
    assert "flops" in ent and "collective_bytes" in ent


@pytest.mark.slow
def test_matches_xla_cost_analysis_on_unrolled():
    """Ground truth check: on an unrolled loop (no whiles), our dot FLOPs
    must match XLA's cost_analysis within 5%."""
    prog = f"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import sys
sys.path.insert(0, {os.path.join(ROOT, 'src')!r})
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.hlo_analysis import analyze_hlo
from repro.parallel.compat import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
def unroll(x, ws):
    for i in range(6):
        x = jnp.tanh(x @ ws[i])
    return x.sum()
xs = jax.ShapeDtypeStruct((64, 128), jnp.float32)
ws = jax.ShapeDtypeStruct((6, 128, 128), jnp.float32)
with mesh:
    c = jax.jit(unroll, in_shardings=(NamedSharding(mesh, P("data", None)),
                                      NamedSharding(mesh, P(None, None, "model")))).lower(xs, ws).compile()
xla = c.cost_analysis()["flops"]
mine = analyze_hlo(c.as_text(), 8).flops
rel = abs(mine - xla) / xla
print("xla", xla, "mine", mine, "rel", rel)
assert rel < 0.05, (xla, mine)
print("COST_MATCH_OK")
"""
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "COST_MATCH_OK" in out.stdout
