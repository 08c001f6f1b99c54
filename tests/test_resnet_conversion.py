"""Model conversion (paper §4.6 / Table 1): spatial == JPEG to float error."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import convert as CV
from repro.core import jpeg as J
from repro.core import resnet as R


@pytest.fixture(scope="module")
def setup():
    spec = R.ResNetSpec(widths=(8, 16, 24), num_classes=10)
    params, state = R.init_resnet(jax.random.PRNGKey(0), spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 3, 32, 32)) * 0.5
    return spec, params, state, x


def _coef(x, spec):
    return jnp.moveaxis(J.jpeg_encode(x, quality=spec.quality, scaled=True), 1, 3)


def test_inference_parity(setup):
    """Paper Table 1: same logits to within float error at exact ReLU."""
    spec, params, state, x = setup
    sp, _ = R.spatial_apply(params, state, x, training=False, spec=spec)
    jp, _ = R.jpeg_apply(params, state, _coef(x, spec), training=False,
                         spec=spec)
    assert np.allclose(sp, jp, atol=1e-4)


def test_training_mode_parity(setup):
    spec, params, state, x = setup
    sp, st_sp = R.spatial_apply(params, state, x, training=True, spec=spec)
    jp, st_jp = R.jpeg_apply(params, state, _coef(x, spec), training=True,
                             spec=spec)
    assert np.allclose(sp, jp, atol=1e-4)
    for k in st_sp:
        assert np.allclose(st_sp[k]["mean"], st_jp[k]["mean"], atol=1e-5)
        assert np.allclose(st_sp[k]["var"], st_jp[k]["var"], atol=1e-4)


def test_convert_and_verify(setup):
    spec, params, state, x = setup
    model, dev = CV.convert_and_verify(params, state, spec, x)
    assert dev < 1e-4
    # precomputed-operator inference path agrees as well
    logits = model(_coef(x, spec))
    sp, _ = R.spatial_apply(params, state, x, training=False, spec=spec)
    assert np.allclose(logits, sp, atol=1e-4)


def test_conversion_degrades_gracefully_with_phi(setup):
    """Paper Fig. 4b: accuracy degrades smoothly as phi decreases."""
    spec, params, state, x = setup
    sp, _ = R.spatial_apply(params, state, x, training=False, spec=spec)
    devs = []
    for phi in (14, 10, 6):
        jp, _ = R.jpeg_apply(params, state, _coef(x, spec), training=False,
                             spec=spec, phi=phi)
        devs.append(float(jnp.max(jnp.abs(sp - jp))))
    assert devs[0] < 1e-4
    assert devs[0] <= devs[1] + 1e-6 <= devs[2] + 2e-6


def test_jpeg_training_step_reduces_loss(setup):
    """Training *in* the JPEG domain (paper §5.3 Fig. 4c regime)."""
    spec, params, state, x = setup
    coef = _coef(x, spec)
    labels = jnp.arange(4) % 10

    def loss_fn(p):
        logits, _ = R.jpeg_apply(p, state, coef, training=True, spec=spec)
        lp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(lp, labels[:, None], axis=1))

    l0, g = jax.value_and_grad(loss_fn)(params)
    p1 = jax.tree.map(lambda p, gg: p - 0.05 * gg, params, g)
    l1 = loss_fn(p1)
    assert float(l1) < float(l0)


def test_from_torch_layout(setup):
    spec, params, state, x = setup
    tensors = {}
    tensors["stem.weight"] = np.asarray(params["stem"]["kernel"])
    for name in ("stem_bn",):
        tensors[f"{name}.weight"] = np.asarray(params[name]["gamma"])
        tensors[f"{name}.bias"] = np.asarray(params[name]["beta"])
        tensors[f"{name}.running_mean"] = np.asarray(state[name]["mean"])
        tensors[f"{name}.running_var"] = np.asarray(state[name]["var"])
    for blk in R._stages(spec):
        name = blk.name
        tensors[f"{name}.conv1.weight"] = np.asarray(params[name]["conv1"])
        tensors[f"{name}.conv2.weight"] = np.asarray(params[name]["conv2"])
        if "proj" in params[name]:
            tensors[f"{name}.proj.weight"] = np.asarray(params[name]["proj"])
        for bn in ("bn1", "bn2"):
            key = f"{name}_{bn}"
            tensors[f"{name}.{bn}.weight"] = np.asarray(params[key]["gamma"])
            tensors[f"{name}.{bn}.bias"] = np.asarray(params[key]["beta"])
            tensors[f"{name}.{bn}.running_mean"] = np.asarray(state[key]["mean"])
            tensors[f"{name}.{bn}.running_var"] = np.asarray(state[key]["var"])
    tensors["head.weight"] = np.asarray(params["head"]["w"]).T
    tensors["head.bias"] = np.asarray(params["head"]["b"])
    p2, s2 = CV.from_torch_layout(tensors, spec)
    jp, _ = R.jpeg_apply(p2, s2, _coef(x, spec), training=False, spec=spec)
    sp, _ = R.spatial_apply(params, state, x, training=False, spec=spec)
    assert np.allclose(jp, sp, atol=1e-4)
