"""The plain reference and the MAC count against the program, on the CPU."""

import copy
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH, ROOT
from reference import cnn_ref

CONFIGS = ("alexnet", "resnet50")


def train_driver():
    import run as harness
    return harness.load_module(BENCH / "drivers" / "train.py")


def load(config: str):
    spec = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    text = (ROOT / spec["recipe"]["net"]).read_text()
    return spec, text


@pytest.mark.parametrize("config", CONFIGS)
def test_macs_and_sizes_match_the_program(config):
    from caffe_mpi_tpu.net import Net
    from caffe_mpi_tpu.proto import NetParameter
    from caffe_mpi_tpu.utils.flops import net_macs_per_image
    spec, text = load(config)
    net = Net(NetParameter.from_text(text), phase="TRAIN")
    macs = cnn_ref.macs_per_sample(cnn_ref.parse_prototxt(text))
    assert macs == net_macs_per_image(net)
    assert macs == spec["sizes"]["forward_macs_per_image"]
    assert spec["sizes"]["learnable_parameters"] == sum(
        math.prod(d.shape) for _, _, d in net.learnable_param_decls())


@pytest.mark.parametrize("config", CONFIGS)
def test_reference_agrees_with_the_net_at_the_tiny_preset(config):
    """f32 agrees to rounding; bf16 is off by about its own rounding, far
    beyond what the f32 run shows, so a tolerance between the two tells a
    cell that silently computes in the lower precision."""
    from caffe_mpi_tpu.net import Net
    from caffe_mpi_tpu.proto import NetParameter
    spec, text = load(config)
    preset, phase = spec["rehearse"], spec["checks"]["logits"]["phase"]
    n, hw = preset["sample"], preset["input_hw"]
    ref_net = cnn_ref.parse_prototxt(text)
    blob = cnn_ref.logits_blob(ref_net, phase)
    npar = NetParameter.from_text(text)
    train_driver().set_input_dims(npar, n, hw)
    feeds = {"data": jax.random.normal(jax.random.PRNGKey(2), (n, 3, *hw)),
             "label": jnp.zeros((n,), jnp.int32)}
    errors = {}
    for precision in ("f32", "bf16"):
        net = Net(copy.deepcopy(npar), phase=phase, precision=precision)
        params, state = net.init(jax.random.PRNGKey(1))
        blobs, _, _ = net.apply(params, state, feeds, train=phase == "TRAIN",
                                rng=jax.random.PRNGKey(3))
        got = np.asarray(blobs[blob].astype(jnp.float32), np.float64)
        want = np.asarray(
            cnn_ref.forward(ref_net, phase, params, state, feeds)[blob],
            np.float64)
        assert got.shape == want.shape == (n, 1000)
        errors[precision] = (np.linalg.norm(got - want)
                             / np.linalg.norm(want))
    assert errors["f32"] < 1e-4
    assert errors["bf16"] > 20 * max(errors["f32"], 1e-5)


def test_pooling_follows_caffe_rounding():
    # 6x6, kernel 3, stride 2: Caffe's ceil gives 3 outputs where floor
    # gives 2, and the last window is clipped to the image
    x = jnp.arange(36, dtype=jnp.float32).reshape(1, 1, 6, 6)
    y = cnn_ref._pooling({"pool": ["MAX"], "kernel_size": [3],
                          "stride": [2]}, x)
    assert y.shape == (1, 1, 3, 3)
    assert float(y[0, 0, 2, 2]) == 35.0
    avg = cnn_ref._pooling({"pool": ["AVE"], "kernel_size": [3],
                            "stride": [2]}, jnp.ones((1, 1, 6, 6)))
    np.testing.assert_allclose(np.asarray(avg), 1.0)


def test_prototxt_parser_keeps_repeats_and_enums():
    net = cnn_ref.parse_prototxt(
        'name: "n"  # comment\n'
        'layer { name: "a" type: "Pooling" bottom: "x" bottom: "y"\n'
        '        pooling_param { pool: AVE global_pooling: true }\n'
        '        include { phase: TEST } }\n')
    layer = net["layer"][0]
    assert layer["bottom"] == ["x", "y"]
    assert layer["pooling_param"][0] == {"pool": ["AVE"],
                                         "global_pooling": [True]}
    assert cnn_ref.layers_for_phase(net, "TRAIN") == []
    assert cnn_ref.layers_for_phase(net, "TEST") == [layer]
