"""Compiled plan execution (``core.plan.compile_plan`` +
``kernels.fused_block`` + ``kernels.tiling``).

Contracts:

* **Fused-block parity sweep** — the compiled schedule matches the
  per-layer ``apply_plan`` walk through stride-1 and stride-2 blocks,
  projection and identity shortcuts, bands ∈ {32, 48, 64} and
  φ ∈ {8, 14}, on the reference (spatial-resident) and pallas
  (megakernel, interpreted) executors;
* the Pallas megakernel body agrees with its packed-operator XLA twin on
  arbitrary inputs (not just band-limited ones);
* **compiled-plan serialization** — save → ``CheckpointManager`` → load
  returns bit-identical logits and an identical schedule;
* factored plans (no materialised Ξ) compile to an all-fallback schedule
  that still matches, and the VMEM budget demotes oversized blocks only
  on the pallas path;
* ``tiling.pick_tile`` sizes row tiles from ``n`` (sublane-aligned,
  balanced) instead of padding small inputs up to the max tile.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import dispatch as DSP
from repro.core import jpeg as J
from repro.core import plan as PL
from repro.core import resnet as R
from repro.kernels import tiling
from repro.kernels.fused_block import fused_block_pallas, \
    fused_block_reference


@pytest.fixture(scope="module")
def setup():
    # widths force a stride-2 + projection block in stage 1; stage 0 is an
    # identity-shortcut stride-1 block.
    spec = R.ResNetSpec(widths=(6, 8), num_classes=10)
    params, state = R.init_resnet(jax.random.PRNGKey(0), spec)
    # randomise every BN so the folds the compiler re-lowers are non-trivial
    key = jax.random.PRNGKey(7)
    for name in params:
        if "_bn" in name or name.endswith("bn"):
            k1, k2, k3, k4, key = jax.random.split(key, 5)
            c = params[name]["gamma"].shape[0]
            params[name]["gamma"] = 1.0 + 0.2 * jax.random.normal(k1, (c,))
            params[name]["beta"] = 0.1 * jax.random.normal(k2, (c,))
            state[name]["mean"] = 0.1 * jax.random.normal(k3, (c,))
            state[name]["var"] = 1.0 + 0.3 * jax.random.uniform(k4, (c,))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 16, 16)) * 0.5
    coef = jnp.moveaxis(J.jpeg_encode(x, quality=spec.quality, scaled=True),
                        1, 3)
    return spec, params, state, coef


@pytest.mark.parametrize("phi", [8, 14])
@pytest.mark.parametrize("bands", [32, 48, 64])
def test_compiled_matches_plan_reference(setup, bands, phi):
    """Spatial-resident fused blocks ≡ the per-layer plan walk, through
    strided/projection and identity blocks, across bands and φ."""
    spec, params, state, coef = setup
    cfg = DSP.DispatchConfig(path="reference", bands=bands)
    plan = PL.build_plan(params, state, spec, phi=phi, dispatch=cfg)
    cp = PL.compile_plan(plan)
    assert cp.meta["fused"] == ["s0b0", "s1b0"]
    strided = cp.blocks[1]
    assert strided.conv1.stride == 2 and strided.proj is not None
    ident = cp.blocks[0]
    assert ident.conv1.stride == 1 and ident.proj is None
    ref = np.asarray(PL.apply_plan(plan, coef))
    got = np.asarray(PL.apply_compiled(cp, coef))
    np.testing.assert_allclose(got, ref, atol=2e-4)
    assert (got.argmax(-1) == ref.argmax(-1)).all()


@pytest.mark.parametrize("bands", [32, 64])
def test_compiled_matches_plan_pallas_interpret(setup, bands):
    """The megakernel (Pallas interpreter) executes the same schedule."""
    spec, params, state, coef = setup
    cfg = DSP.DispatchConfig(path="pallas", bands=bands, interpret=True)
    plan = PL.build_plan(params, state, spec, dispatch=cfg)
    cp = PL.compile_plan(plan)
    assert cp.meta["path"] == "pallas" and cp.meta["fused"]
    ref = np.asarray(PL.apply_plan(plan, coef))
    got = np.asarray(PL.apply_compiled(cp, coef))
    np.testing.assert_allclose(got, ref, atol=2e-4)
    assert (got.argmax(-1) == ref.argmax(-1)).all()


def test_tpu_rule_schedules_packed_gemm_twin(setup, monkeypatch):
    """On a TPU compile_plan never schedules the megakernel (Mosaic refuses
    it): the rule is recorded in meta, fused steps resolve to the packed
    GEMM twin, and that schedule still matches the plan walk."""
    spec, params, state, coef = setup
    plan = PL.build_plan(params, state, spec,
                         dispatch=DSP.DispatchConfig(bands=48))
    ref = np.asarray(PL.apply_plan(plan, coef))
    monkeypatch.setattr(DSP, "platform", lambda: "tpu")
    cp = PL.compile_plan(plan)
    assert cp.meta["platform"] == "tpu"
    assert cp.meta["rules"] == {"megakernel": PL.MEGAKERNEL_OFF_TPU}
    assert cp.meta["path"] == "gemm"
    assert cp.meta["fused"] == ["s0b0", "s1b0"]
    assert all(b.path == "gemm" for b in cp.blocks)
    assert {PL.step_executor(cp, b.name)[1] for b in cp.blocks} \
        == {"gemm"}
    monkeypatch.undo()
    got = np.asarray(PL.apply_compiled(cp, coef))
    np.testing.assert_allclose(got, ref, atol=2e-4)
    assert (got.argmax(-1) == ref.argmax(-1)).all()


@pytest.mark.parametrize("path,interpret,executor,want", [
    ("pallas", True, None, "pallas"),
    ("pallas", None, None, "spatial"),
    ("reference", None, None, "spatial"),
    ("reference", None, "gemm", "gemm"),
])
def test_step_executor_names_what_runs(setup, monkeypatch, path, interpret,
                                       executor, want):
    """plan.step_executor (what introspection and the serve report name)
    is the body each fused step actually runs — including a pallas path
    that delegates off-TPU."""
    import repro.kernels.fused_block as FB
    import repro.kernels.ops as KO

    spec, params, state, coef = setup
    cfg = DSP.DispatchConfig(path=path, bands=48, interpret=interpret)
    cp = PL.compile_plan(PL.build_plan(params, state, spec, dispatch=cfg))
    ran = []
    for mod, name, label in ((FB, "fused_block_reference", "gemm"),
                             (FB, "fused_block_spatial", "spatial"),
                             (KO, "fused_block", "pallas")):
        def spy(*a, _real=getattr(mod, name), _label=label, **k):
            ran.append(_label)
            return _real(*a, **k)

        monkeypatch.setattr(mod, name, spy)
    PL.apply_compiled(cp, coef, executor=executor)
    assert set(ran) == {want}
    assert {PL.step_executor(cp, b.name, executor) for b in cp.blocks} \
        == {("fused", want)}


def test_megakernel_matches_packed_xla_twin(setup):
    """fused_block_pallas ≡ fused_block_reference on arbitrary packed
    inputs (both shortcut kinds), not only band-limited ones."""
    spec, params, state, coef = setup
    cfg = DSP.DispatchConfig(path="pallas", bands=48, interpret=True)
    cp = PL.compile_plan(PL.build_plan(params, state, spec, dispatch=cfg))
    key = jax.random.PRNGKey(3)
    grid = {"s0b0": 2, "s1b0": 2}
    for blk in cp.blocks:
        assert blk.kind == "fused"
        bh = grid[blk.name]
        x = jax.random.normal(key, (3, bh, bh, blk.cin * blk.w_in))
        want = fused_block_reference(x, blk.conv1, blk.asm_mid, blk.conv2,
                                     blk.asm_out, blk.proj)
        got = fused_block_pallas(x, blk.conv1, blk.asm_mid, blk.conv2,
                                 blk.asm_out, blk.proj, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, err_msg=blk.name)


def test_compiled_roundtrip_bit_identical(setup, tmp_path):
    """save_compiled_plan → CheckpointManager → load_compiled_plan serves
    bit-identical logits with an identical schedule."""
    spec, params, state, coef = setup
    cfg = DSP.DispatchConfig(path="reference", bands=40)
    cp = PL.compile_plan(PL.build_plan(params, state, spec, dispatch=cfg))
    before = np.asarray(PL.apply_compiled(cp, coef))
    PL.save_compiled_plan(cp, str(tmp_path))
    restored = PL.load_compiled_plan(str(tmp_path))
    assert restored.spec == cp.spec
    assert restored.bands == cp.bands
    assert restored.meta == cp.meta
    assert [b.kind for b in restored.blocks] == [b.kind for b in cp.blocks]
    after = np.asarray(PL.apply_compiled(restored, coef))
    np.testing.assert_array_equal(before, after)


def test_load_compiled_rejects_foreign_checkpoint(tmp_path):
    from repro.checkpoint import CheckpointManager

    CheckpointManager(str(tmp_path)).save(0, {"w": np.ones(3)})
    with pytest.raises(ValueError, match="compiled plan"):
        PL.load_compiled_plan(str(tmp_path))


def test_factored_plan_compiles_to_fallback(setup):
    """No materialised Ξ → every step stays on the per-layer walk, and the
    compiled schedule still matches the plan."""
    spec, params, state, coef = setup
    cfg = DSP.DispatchConfig(path="factored", bands=32)
    plan = PL.build_plan(params, state, spec, dispatch=cfg)
    cp = PL.compile_plan(plan)
    assert cp.meta["fused"] == []
    assert set(cp.meta["layers"]) == {"stem", "s0b0", "s1b0"}
    np.testing.assert_allclose(np.asarray(PL.apply_compiled(cp, coef)),
                               np.asarray(PL.apply_plan(plan, coef)),
                               atol=1e-5)


def test_vmem_budget_gates_pallas_only(setup):
    """An undersized budget demotes pallas blocks to the per-layer walk
    (the megakernel's operands must fit VMEM) but never reference blocks
    (the XLA executor has no such limit)."""
    spec, params, state, coef = setup
    pcfg = DSP.DispatchConfig(path="pallas", bands=32, interpret=True)
    plan = PL.build_plan(params, state, spec, dispatch=pcfg)
    cp = PL.compile_plan(plan, vmem_budget=1)
    assert cp.meta["fused"] == []
    assert all("vmem" in reason for name, reason in cp.meta["layers"].items()
               if name != "stem")
    np.testing.assert_allclose(np.asarray(PL.apply_compiled(cp, coef)),
                               np.asarray(PL.apply_plan(plan, coef)),
                               atol=1e-4)
    rcfg = DSP.DispatchConfig(path="reference", bands=32)
    cp_ref = PL.compile_plan(PL.build_plan(params, state, spec,
                                           dispatch=rcfg), vmem_budget=1)
    assert cp_ref.meta["fused"] == ["s0b0", "s1b0"]


def test_compile_for_inference_wrapper(setup):
    spec, params, state, coef = setup
    cfg = DSP.DispatchConfig(path="reference", bands=48)
    cp = R.compile_for_inference(params, state, spec, dispatch=cfg)
    plan = PL.build_plan(params, state, spec, dispatch=cfg)
    np.testing.assert_allclose(np.asarray(cp(coef)),
                               np.asarray(PL.apply_plan(plan, coef)),
                               atol=2e-4)


def test_pick_tile_sizes_from_input():
    """Tiles are balanced, sublane-aligned, and never waste >1 sublane of
    rows — a single-image request no longer pads up to the max tile."""
    for n in (1, 5, 16, 128, 1000, 1024, 1040, 5000):
        tile = tiling.pick_tile(n, 1024)
        assert tile <= 1024 and tile % tiling.SUBLANE == 0 or tile == n
        num = -(-n // tile)
        waste = num * tile - n
        assert waste < tiling.SUBLANE + tile / 8, (n, tile, waste)
    assert tiling.pick_tile(16, 1024) == 16      # small input: own tile
    assert tiling.pick_tile(1040, 1024) == 520   # balanced split, no pad
    with pytest.raises(ValueError):
        tiling.pick_tile(0, 1024)


def test_asm_relu_kernel_small_input_no_max_tile_pad():
    """The asm_relu kernel's tile now follows the input size."""
    from repro.core import asm as asmlib
    from repro.kernels import ops as kops

    coef = jax.random.normal(jax.random.PRNGKey(2), (3, 2, 2, 4, 64)) * 0.4
    want = asmlib.asm_relu(coef, 8)
    got = kops.asm_relu(coef, 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
