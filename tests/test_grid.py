"""Plan-grid capture engine (``repro.serving.grid``).

Contracts:

* **bucket math is aphrodite-equivalent** — capture schedule 1, 2, 4,
  multiples of 8; a batch runs in the smallest covering bucket
  (1→1, 3→4, 9→16, 17→24 …), and the scheduler's full batch size always
  has a cell;
* **cells are exact** — a bucket cell's first ``n`` logits match the
  compiled plan applied to the unpadded batch (zero pad rows are
  row-independent), and a grid rebuilt from a restored ladder manifest
  produces bit-identical outputs per cell;
* **donation is safe** — the captured executable consumes its input
  buffer (enforced backends delete it; reuse raises) while the pinned
  host staging buffer stays reusable across calls;
* **warmup closes the shape set** — after the grid sweep, steady-state
  serving performs zero JIT compiles (``compiles_post_warmup == 0``)
  and partial batches pad only to the covering bucket
  (``padding_fraction`` in the report);
* **QoS estimates key per cell** — a bucket-1 trickle is not judged by
  bucket-8 latency under deadline pressure.
"""
import threading

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import dispatch as DSP
from repro.core import jpeg as J
from repro.core import plan as PL
from repro.core import resnet as R
from repro import serving as SV
from repro.serving.qos import QosPolicy, TierSelector

EXECUTOR = None if jax.default_backend() == "tpu" else "gemm"


@pytest.fixture(scope="module")
def setup():
    spec = R.ResNetSpec(widths=(6, 8), num_classes=10)
    params, state = R.init_resnet(jax.random.PRNGKey(0), spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 3, 16, 16)) * 0.5
    coef = jnp.moveaxis(J.jpeg_encode(x, quality=spec.quality, scaled=True),
                        1, 3)
    plan = PL.build_plan(params, state, spec,
                         dispatch=DSP.DispatchConfig(path="reference"))
    return spec, coef, plan


# --------------------------------------------------------------------------
# Bucket math
# --------------------------------------------------------------------------


def test_batch_buckets_schedule():
    assert SV.batch_buckets(1) == (1,)
    assert SV.batch_buckets(2) == (1, 2)
    assert SV.batch_buckets(4) == (1, 2, 4)
    assert SV.batch_buckets(8) == (1, 2, 4, 8)
    assert SV.batch_buckets(24) == (1, 2, 4, 8, 16, 24)
    assert SV.batch_buckets(64) == (1, 2, 4, 8, 16, 24, 32, 40, 48, 56, 64)
    # a max_batch off the schedule is always captured itself
    assert SV.batch_buckets(6) == (1, 2, 4, 6)
    assert SV.batch_buckets(12) == (1, 2, 4, 8, 12)
    with pytest.raises(ValueError):
        SV.batch_buckets(0)


@pytest.mark.parametrize("n,want", [
    # the aphrodite _get_graph_batch_size equivalence table
    (1, 1), (2, 2), (3, 4), (4, 4), (5, 8), (8, 8),
    (9, 16), (16, 16), (17, 24), (24, 24), (25, 32),
])
def test_bucket_for_covering(n, want):
    assert SV.bucket_for(n, SV.batch_buckets(32)) == want


def test_bucket_for_rejects_uncovered():
    with pytest.raises(ValueError):
        SV.bucket_for(9, SV.batch_buckets(8))
    with pytest.raises(ValueError):
        SV.bucket_for(0, SV.batch_buckets(8))


def test_validate_and_cover_buckets():
    with pytest.raises(ValueError):
        SV.validate_buckets(())
    with pytest.raises(ValueError):
        SV.validate_buckets((2, 2))
    with pytest.raises(ValueError):
        SV.validate_buckets((4, 2))
    with pytest.raises(ValueError):
        SV.validate_buckets((0, 2))
    assert SV.cover_buckets(None, 12) == SV.batch_buckets(12)
    # explicit lists are clipped to the batch and always cover it
    assert SV.cover_buckets((1, 2, 4, 8, 16), 8) == (1, 2, 4, 8)
    assert SV.cover_buckets((1, 2), 6) == (1, 2, 6)
    assert SV.cover_buckets((8,), 8) == (8,)


# --------------------------------------------------------------------------
# Cell exactness + manifest round trip
# --------------------------------------------------------------------------


def test_cell_matches_unpadded_compiled_plan(setup):
    """The covering cell's first n logits == apply_compiled on the
    unpadded batch (zero pad rows are row-independent)."""
    spec, coef, plan = setup
    ladder = SV.build_ladder(plan, caps=(None, 16))
    g = SV.PlanGrid(ladder, batch=8, grid=tuple(coef.shape[1:3]),
                    channels=int(coef.shape[3]), executor=EXECUTOR)
    assert g.buckets == (1, 2, 4, 8)
    for tier_ix in (0, 1):
        cp = ladder.tiers[tier_ix].compiled
        col = g.columns[tier_ix]
        for n in (1, 3, 5, 8):
            rows = np.asarray(coef[:n], np.float32)
            want = np.asarray(PL.apply_compiled(cp, jnp.asarray(rows),
                                                executor=EXECUTOR))
            got = np.asarray(col.coef_fn(rows))
            assert got.shape[0] == g.bucket_for(n)
            np.testing.assert_allclose(got[:n], want, atol=1e-5)


def test_cell_lower_is_the_captured_entry(setup):
    """GridCell.lower lowers the cell's own captured entry: pinned to the
    bucket's shape, the schedule's arrays passed as arguments rather than
    baked in, and the bucket's logits out; lowering serves nothing."""
    spec, coef, plan = setup
    g = SV.PlanGrid(SV.build_ladder(plan, caps=(None,)), batch=4,
                    grid=tuple(coef.shape[1:3]), channels=int(coef.shape[3]),
                    executor=EXECUTOR)
    col = g.columns[0]
    col.coef_fn(np.asarray(coef[:3], np.float32))
    cell = col.cells[("coefficients", 4)]
    lowered = cell.lower()
    (x_info, arrays_info), _ = lowered.args_info
    assert x_info.shape == (4, *coef.shape[1:])
    assert len(arrays_info) == len(
        [a for a in jax.tree_util.tree_leaves(col.compiled)
         if isinstance(a, (jax.Array, np.ndarray))])
    assert lowered.out_info.shape == (4, spec.num_classes)
    assert "ENTRY" in lowered.compile().as_text()
    assert cell.hits == 1


def test_grid_manifest_roundtrip_bit_exact(setup, tmp_path):
    """Ladder manifest persists the capture buckets; a grid rebuilt from
    the restored ladder serves bit-identical logits per cell."""
    spec, coef, plan = setup
    ladder = SV.build_ladder(plan, caps=(None, 16), buckets=(1, 2, 4))
    d = str(tmp_path / "plan")
    SV.save_ladder(ladder, d)
    restored = SV.load_ladder(d)
    assert restored.buckets == (1, 2, 4)
    kw = dict(batch=4, grid=tuple(coef.shape[1:3]),
              channels=int(coef.shape[3]), executor=EXECUTOR)
    g0 = SV.PlanGrid(ladder, **kw)
    g1 = SV.PlanGrid(restored, **kw)
    assert g0.buckets == g1.buckets == (1, 2, 4)
    for tier_ix in range(len(ladder.tiers)):
        for n in (1, 3, 4):
            rows = np.asarray(coef[:n], np.float32)
            a = np.asarray(g0.columns[tier_ix].coef_fn(rows))
            b = np.asarray(g1.columns[tier_ix].coef_fn(rows))
            assert np.array_equal(a, b)


def test_captured_entry_rejects_foreign_shape(setup):
    """A captured executable is pinned: a different shape raises instead
    of silently retracing."""
    spec, coef, plan = setup
    cp = PL.compile_plan(plan)
    fn = PL.capture_compiled(cp, (2, *coef.shape[1:]), executor=EXECUTOR)
    np.asarray(fn(jnp.asarray(coef[:2])))
    with pytest.raises(ValueError, match="pinned"):
        fn(jnp.asarray(coef[:3]))


# --------------------------------------------------------------------------
# Donation safety + pinned staging reuse
# --------------------------------------------------------------------------


def test_donated_input_not_reusable_after_call(setup):
    spec, coef, plan = setup
    cp = PL.compile_plan(plan)
    fn = PL.capture_compiled(cp, (2, *coef.shape[1:]), executor=EXECUTOR,
                             donate=True)
    x = jnp.array(coef[:2])
    out = np.asarray(fn(x))
    assert np.isfinite(out).all()
    if not x.is_deleted():
        pytest.skip("backend does not enforce buffer donation")
    with pytest.raises(RuntimeError):
        fn(x)  # the donated buffer is gone — reuse must fail loudly


def test_cell_staging_buffer_survives_donation(setup):
    """GridCell stages into a pinned host buffer and copies to device, so
    repeated calls through the same cell never trip over the donation —
    and different payloads through the same staging buffer stay exact."""
    spec, coef, plan = setup
    ladder = SV.build_ladder(plan, caps=(None,))
    g = SV.PlanGrid(ladder, batch=4, grid=tuple(coef.shape[1:3]),
                    channels=int(coef.shape[3]), executor=EXECUTOR)
    col = g.columns[0]
    cp = ladder.tiers[0].compiled
    for i in range(4):  # same cell, same staging buffer, fresh rows
        rows = np.asarray(coef[i:i + 1], np.float32)
        want = np.asarray(PL.apply_compiled(cp, jnp.asarray(rows),
                                            executor=EXECUTOR))
        got = np.asarray(col.coef_fn(rows))
        np.testing.assert_allclose(got[:1], want, atol=1e-5)
    cell = col.cells[("coefficients", 1)]
    assert cell.hits == 4
    # one staging buffer per distinct shape, not per call
    assert len(g.pool) == 1


def test_cell_rejects_oversized_or_foreign_rows(setup):
    spec, coef, plan = setup
    ladder = SV.build_ladder(plan, caps=(None,))
    g = SV.PlanGrid(ladder, batch=2, grid=tuple(coef.shape[1:3]),
                    channels=int(coef.shape[3]), executor=EXECUTOR)
    cell = g.columns[0].cell("coefficients", 2, coef.shape[1:])
    with pytest.raises(ValueError, match="serves shape"):
        cell(np.asarray(coef[:3], np.float32))  # over the bucket
    with pytest.raises(ValueError, match="serves shape"):
        cell(np.zeros((1, 1, 1, 3, 64), np.float32))  # wrong item shape


# --------------------------------------------------------------------------
# QoS: per-cell latency estimates
# --------------------------------------------------------------------------


def test_selector_keys_latency_per_bucket():
    """Deadline pressure is judged against the latency of the cell the
    batch will actually run in: a cheap bucket-1 trickle must not be
    degraded because bucket-8 batches are slow (and vice versa)."""
    sel = TierSelector(2, QosPolicy(hysteresis=1))
    sel.observe(0, 0.5, bucket=8)    # full batches: 500ms
    sel.observe(0, 0.01, bucket=1)   # singles: 10ms
    assert sel.est_latency(0, 1) == pytest.approx(0.01)
    assert sel.est_latency(0, 8) == pytest.approx(0.5)
    # 100ms of slack: fine for a single, hopeless for a full batch
    assert sel.select(pending=2, batch=8, head_slack_s=0.1, bucket=1) == 0
    assert sel.select(pending=2, batch=8, head_slack_s=0.1, bucket=8) == 1


def test_selector_bucket_estimates_fall_back_sensibly():
    sel = TierSelector(3, QosPolicy(hysteresis=1))
    sel.observe(1, 0.2, bucket=4)
    # same tier, nearest bucket
    assert sel.est_latency(1, 8) == pytest.approx(0.2)
    # neighbour tier when the tier has no observations at all
    assert sel.est_latency(0, 4) == pytest.approx(0.2)
    # wildcard read prefers the largest observed bucket (conservative)
    sel.observe(1, 0.05, bucket=1)
    assert sel.est_latency(1) == pytest.approx(0.2)
    # pre-grid wildcard observations still resolve exactly
    sel2 = TierSelector(2, QosPolicy(hysteresis=1))
    sel2.observe(0, 0.3)
    assert sel2.est_latency(0) == pytest.approx(0.3)
    assert sel2.est_latency(0, 8) == pytest.approx(0.3)


# --------------------------------------------------------------------------
# Scheduler integration: zero post-warmup compiles, bucketed padding
# --------------------------------------------------------------------------


def _sched(plan, coef, **kw):
    ladder = kw.pop("ladder", None) or SV.build_ladder(plan, caps=(None, 16))
    kw.setdefault("batch", 4)
    kw.setdefault("grid", tuple(coef.shape[1:3]))
    kw.setdefault("channels", int(coef.shape[3]))
    return SV.BandElasticScheduler(ladder, **kw)


def test_scheduler_zero_compiles_after_warmup(setup):
    """The warmup sweep closes the compiled-shape set: a mixed-occupancy
    stream (singles, partial batches, full batches) performs zero JIT
    compiles, every batch lands in its covering bucket's cell, and the
    padding waste is visible in the report."""
    spec, coef, plan = setup
    with _sched(plan, coef) as s:
        s.warmup(kinds=("coefficients",))
        warm = s.metrics.report()
        # 2 distinct tier columns x buckets (1, 2, 4), coefficients only
        assert s.buckets == (1, 2, 4)
        assert warm["compiles_total"] == 6
        assert warm["compiles_post_warmup"] == 0

        reqs = []
        for _ in range(3):  # trickle: one at a time, fully drained
            r = s.submit(np.asarray(coef[0]))
            r.result(timeout=60)
            reqs.append(r)
        with s._lock:  # a 3-deep group dispatched as one take → bucket 4
            for i in range(3):
                reqs.append(SV.ServeRequest(9000 + i, "coefficients",
                                            np.asarray(coef[i]), None))
                s._queues["coefficients"].append(reqs[-1])
            s._work.notify_all()
        for i in range(8):  # saturating tail
            reqs.append(s.submit(np.asarray(coef[i % coef.shape[0]])))
        s.drain(timeout=120)
    assert all(r.done() for r in reqs)
    rep = s.metrics.report()
    assert rep["compiles_total"] == 6          # nothing new compiled
    assert rep["compiles_post_warmup"] == 0
    assert "post_warmup_compiles" not in rep
    hits = rep["grid_cell_hits"]
    assert hits and all("/coefficients/b" in k for k in hits)
    assert sum(hits.values()) == sum(
        t["batches"] for t in rep["per_tier"].values())
    # the trickle ran in bucket 1 (no pad-to-max), the group padded 3→4
    assert any(k.endswith("/b1") for k in hits)
    assert rep["padding_fraction"] is not None
    assert 0.0 <= rep["padding_fraction"] < 1.0


def test_scheduler_lazy_compile_is_counted_post_warmup(setup):
    """An unwarmed kind that compiles mid-traffic is not silent: the
    compile accounting reports it (this is exactly what the CI
    zero-compile assertion would catch)."""
    spec, coef, plan = setup
    with _sched(plan, coef, batch=2) as s:
        s.warmup(kinds=())   # declare warm without compiling anything
        s.submit(np.asarray(coef[0])).result(timeout=60)
    rep = s.metrics.report()
    assert rep["compiles_total"] == 1
    assert rep["compiles_post_warmup"] == 1
    assert rep["post_warmup_compiles"] == ["top/coefficients/b1"]


def test_scheduler_fixed_bucket_reproduces_pad_to_max(setup):
    """buckets=(batch,) is the pre-grid behaviour: every batch pads to
    the full slot count."""
    spec, coef, plan = setup
    with _sched(plan, coef, buckets=(4,)) as s:
        assert s.buckets == (4,)
        s.submit(np.asarray(coef[0])).result(timeout=60)
    rep = s.metrics.report()
    assert rep["padding_fraction"] == pytest.approx(0.75)
    assert list(rep["grid_cell_hits"]) == ["top/coefficients/b4"]


def test_scheduler_bytes_grid_cells(setup):
    """bytes traffic routes through packed cells of the covering bucket
    and stays compile-free after a bytes warmup."""
    from repro.codec import encode_pixels
    from repro.core import dct as dctlib

    spec, coef, plan = setup
    rng = np.random.default_rng(3)
    qt = np.rint(dctlib.quantization_table(
        75, dc_is_mean=False)).astype(np.int64)
    datas = [encode_pixels(
        np.clip(rng.normal(0, 0.3, (3, 16, 16)), -1.0, 127.0 / 128.0),
        qtable=qt) for _ in range(5)]
    with _sched(plan, coef) as s:
        s.warmup(kinds=("bytes",))
        compiled_at_warmup = s.metrics.report()["compiles_total"]
        reqs = [s.submit(d, kind="bytes") for d in datas]
        outs = [r.result(timeout=60) for r in reqs]
    assert all(np.isfinite(o).all() for o in outs)
    rep = s.metrics.report()
    assert rep["compiles_total"] == compiled_at_warmup
    assert rep["compiles_post_warmup"] == 0
    assert all("/bytes/b" in k for k in rep["grid_cell_hits"])


def test_grid_warmup_and_summary(setup):
    spec, coef, plan = setup
    ladder = SV.build_ladder(plan, caps=(None, 16))
    g = SV.PlanGrid(ladder, batch=4, grid=tuple(coef.shape[1:3]),
                    channels=int(coef.shape[3]), executor=EXECUTOR)
    g.warmup(kinds=("coefficients",))
    summ = g.summary()
    assert summ["buckets"] == [1, 2, 4]
    assert summ["distinct_columns"] == 2
    assert summ["cells"] == 6
    assert summ["host_staging_bytes"] > 0
    assert set(g.cell_hits()) == {
        f"{t}/coefficients/b{b}" for t in ("top", "b16") for b in (1, 2, 4)}
