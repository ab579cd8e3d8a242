"""The paper's enhancer network: size claim, regulation range, ablations."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


@pytest.mark.parametrize("hw", [(16, 16), (24, 20), (25, 33), (31, 17)])
@pytest.mark.parametrize("cin,cout", [(1, 4), (4, 6), (8, 8), (12, 4),
                                      (8, 1)])
@pytest.mark.parametrize("stride", [1, 2])
def test_conv_taps_match_xla_conv(hw, cin, cout, stride):
    """Every conv layer of the forward is nine shifted HIGHEST-precision
    GEMMs (C_out=1 padded to a two-column GEMM); across odd extents,
    strides and channel counts it agrees with XLA's SAME conv."""
    rng = np.random.default_rng(hash((hw, cin, cout, stride)) % 2**32)
    x = jnp.asarray(rng.standard_normal((2, *hw, cin)), jnp.float32)
    p = {"w": jnp.asarray(rng.standard_normal((3, 3, cin, cout)) * 0.2,
                          jnp.float32),
         "b": jnp.asarray(rng.standard_normal((cout,)) * 0.1, jnp.float32)}
    y = SD._conv(x, p, stride=stride)
    yr = jax.lax.conv_general_dilated(
        x, p["w"], (stride, stride), "SAME", dimension_numbers=SD._DN,
        precision=jax.lax.Precision.HIGHEST) + p["b"]
    assert y.shape == yr.shape
    assert np.allclose(np.asarray(y), np.asarray(yr), atol=1e-5)
