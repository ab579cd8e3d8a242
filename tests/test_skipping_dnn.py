"""The paper's enhancer network: size claim, regulation range, ablations."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import batched_engine, online_trainer
from repro.core import skipping_dnn as SD


def test_param_count_matches_paper_claim():
    """~3,000 params for the 10-layer single-field net (paper §3.2.2)."""
    cfg = SD.SkippingDNNConfig(c_in=1)
    params = SD.init_params(jax.random.PRNGKey(0), cfg)
    n = SD.param_count(params)
    assert 2500 <= n <= 3500, n


def test_cross_field_only_adds_input_channel_params():
    p1 = SD.init_params(jax.random.PRNGKey(0), SD.SkippingDNNConfig(c_in=1))
    p2 = SD.init_params(jax.random.PRNGKey(0), SD.SkippingDNNConfig(c_in=2))
    assert SD.param_count(p2) - SD.param_count(p1) == 9 * 4  # 3x3 conv, 4 ch


def test_regulated_output_in_unit_range():
    cfg = SD.SkippingDNNConfig(c_in=1, regulated=True)
    params = SD.init_params(jax.random.PRNGKey(1), cfg)
    x = np.random.default_rng(0).standard_normal((3, 40, 40, 1)).astype(np.float32) * 10
    out = np.asarray(SD.forward(params, x, regulated=True, skip=True))
    assert out.shape == (3, 40, 40, 1)
    # closed interval: sigmoid saturates to exactly 0/1 in fp32 for large
    # |z|, giving residuals of exactly ±eb — still within the 2x bound
    assert np.all(out >= -1.0) and np.all(out <= 1.0)


def test_unregulated_output_unbounded_head():
    cfg = SD.SkippingDNNConfig(c_in=1, regulated=False)
    params = SD.init_params(jax.random.PRNGKey(1), cfg)
    x = np.random.default_rng(0).standard_normal((2, 32, 32, 1)).astype(np.float32)
    out = np.asarray(SD.forward(params, x, regulated=False, skip=True))
    assert np.isfinite(out).all()


def test_arbitrary_hw_padding():
    cfg = SD.SkippingDNNConfig(c_in=1)
    params = SD.init_params(jax.random.PRNGKey(0), cfg)
    for hw in [(17, 23), (16, 16), (50, 33)]:
        x = np.zeros((1, *hw, 1), np.float32)
        out = SD.forward(params, x, regulated=True, skip=True)
        assert out.shape == (1, *hw, 1)


def test_non_skipping_variant_runs():
    cfg = SD.SkippingDNNConfig(c_in=1, skip=False)
    params = SD.init_params(jax.random.PRNGKey(0), cfg)
    x = np.zeros((1, 32, 32, 1), np.float32)
    out = SD.forward(params, x, regulated=True, skip=False)
    assert out.shape == (1, 32, 32, 1)


_HW = [(16, 16), (24, 20), (25, 33), (31, 17)]
_CHANNELS = [(1, 4), (4, 6), (8, 8), (12, 4), (8, 1)]


def _layer(hw, cin, cout, seed):
    """A channels-first input ``[C_in, 2, H, W]`` and a 3×3 layer's params."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((cin, 2, *hw)), jnp.float32)
    p = {"w": jnp.asarray(rng.standard_normal((3, 3, cin, cout)) * 0.2,
                          jnp.float32),
         "b": jnp.asarray(rng.standard_normal((cout,)) * 0.1, jnp.float32)}
    return x, p


@pytest.mark.parametrize("hw", _HW)
@pytest.mark.parametrize("cin,cout", _CHANNELS)
@pytest.mark.parametrize("stride", [1, 2])
def test_conv_taps_match_xla_conv(hw, cin, cout, stride):
    """Every conv layer of the forward is nine shifted channels-first f32
    multiply-and-sum taps; across odd extents, strides and channel counts
    (C_out=1 included) it agrees with XLA's SAME conv."""
    x, p = _layer(hw, cin, cout, hash((hw, cin, cout, stride)) % 2**32)
    y = SD._conv(x, p, stride=stride)
    yr = jax.lax.conv_general_dilated(
        x, p["w"], (stride, stride), "SAME", dimension_numbers=SD._DN,
        precision=jax.lax.Precision.HIGHEST) + p["b"][:, None, None, None]
    assert y.shape == yr.shape
    assert np.allclose(np.asarray(y), np.asarray(yr), atol=1e-5)


@pytest.mark.parametrize("hw", _HW)
@pytest.mark.parametrize("cin,cout", _CHANNELS)
def test_deconv_matches_xla_conv_transpose(hw, cin, cout):
    """The stride-2 transpose conv's four parity planes, interleaved by
    ``_merge``, agree with XLA's SAME ``conv_transpose`` at odd extents."""
    x, p = _layer(hw, cin, cout, hash((hw, cin, cout, 3)) % 2**32)
    y = SD._merge(SD._deconv(x, p))
    yr = jax.lax.conv_transpose(
        x, p["w"], (2, 2), "SAME", dimension_numbers=SD._DN,
        precision=jax.lax.Precision.HIGHEST) + p["b"][:, None, None, None]
    assert y.shape == yr.shape == (cout, 2, 2 * hw[0], 2 * hw[1])
    assert np.allclose(np.asarray(y), np.asarray(yr), atol=1e-5)


@pytest.mark.parametrize("hw", [(16, 16), (24, 20), (32, 18)])
@pytest.mark.parametrize("cin,cout", _CHANNELS)
def test_split_and_merge_are_the_parity_planes(hw, cin, cout):
    """``_split`` gives plane ``2*py + px`` = ``x[..., py::2, px::2]`` (what
    a stride-2 conv's windows are cut from) and ``_merge`` undoes it."""
    x, _ = _layer(hw, cin, cout, hash((hw, cin, cout, 4)) % 2**32)
    xs = np.asarray(SD._split(x))
    assert xs.shape == (cin, 4, 2, hw[0] // 2, hw[1] // 2)
    for py in range(2):
        for px in range(2):
            assert np.array_equal(xs[:, 2 * py + px],
                                  np.asarray(x)[..., py::2, px::2])
    assert np.asarray(SD._merge(xs)).tobytes() == np.asarray(x).tobytes()


@pytest.mark.parametrize("hw", [(32, 32), (48, 40)])
def test_value_and_grad_bit_identical_under_vmap(hw):
    """``value_and_grad`` of the training loss at ``c_in=2``: the parity
    probe's two single-field calls and one ``vmap``-over-fields call agree
    byte for byte (the stacked training strategy's contract), and the
    forward stays within 1e-5 of the XLA-conv oracle."""
    net = SD.SkippingDNNConfig(c_in=2)
    assert batched_engine.vmap_bit_parity(net, hw, 4,
                                          online_trainer.TrainConfig())
    params = SD.init_params(jax.random.PRNGKey(3), net)
    x = jnp.asarray(np.random.default_rng(hash(hw) % 2**32)
                    .standard_normal((4, *hw, 2)), jnp.float32)
    out = np.asarray(SD.forward(params, x))
    ref = np.asarray(SD.forward_reference(params, x))
    assert out.shape == (4, *hw, 1)
    assert np.max(np.abs(out - ref)) < 1e-5
