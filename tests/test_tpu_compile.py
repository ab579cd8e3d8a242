"""Compile the main path's Pallas kernels and the training step for a TPU
v5e that is described, not attached.

The CPU suite runs the kernels in interpret mode, which accepts programs the
TPU compiler refuses (i64 block indices under x64, unsupported primitives,
blocks that overflow scoped VMEM).  These tests hand the real compiler the
real widths — 512² planes, x64 on as the package runs — and check that a
Mosaic kernel (``tpu_custom_call``) comes out.  Nothing runs, so they say
nothing about results or speed.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU compiler library, and every
test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro  # noqa: F401  (turns x64 on, as every entry point does)
from repro.core import online_trainer, skipping_dnn
from repro.kernels import dispatch, ops
from repro.kernels.fused_enhance import fused_enhance
from repro.kernels.lorenzo3d import lorenzo3d_fwd, lorenzo3d_inv
from repro.optim import adamw_init

W = 512                    # the paper's runtime block: 512² planes


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _compile(fn, *shapes):
    assert jax.config.jax_enable_x64, "the package runs with x64 on"
    return jax.jit(fn).lower(*shapes).compile()


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_lorenzo_fwd_compiles(one_chip, no_persistent_cache):
    tz = ops.fwd_tz(W, W, W)
    c = _compile(lambda x: lorenzo3d_fwd(x, 1e-3, tz=tz, interpret=False),
                 _sds(one_chip, (2 * tz, W, W), jnp.float32))
    assert "tpu_custom_call" in c.as_text()


def test_lorenzo_inv_compiles(one_chip, no_persistent_cache):
    tz = ops.inv_tz(W, W, W)
    c = _compile(lambda d: lorenzo3d_inv(d, tz=tz, interpret=False),
                 _sds(one_chip, (2 * tz, W, W), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_lorenzo_wrapper_compiles_at_ragged_depth(one_chip,
                                                  no_persistent_cache):
    # The ops.py wrapper's own pad/crop around the kernel, with a depth that
    # is not a multiple of the slab and a plane that is not (8, 128)-tiled.
    with dispatch.force_backend("tpu"):
        c = _compile(lambda x: ops.lorenzo_quantize(x, 1e-3),
                     _sds(one_chip, (5, 500, W - 1), jnp.float32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("regulated,strict", [(True, True), (False, True),
                                              (False, False)])
def test_fused_enhance_compiles(one_chip, no_persistent_cache, regulated,
                                strict):
    spec = _sds(one_chip, (2 * 256, W), jnp.float32)
    c = _compile(lambda z, d, o: fused_enhance(
        z, d, o, 1e-3, regulated=regulated, strict=strict, tr=256,
        interpret=False), spec, spec, spec)
    assert "tpu_custom_call" in c.as_text()


def test_training_epoch_compiles(one_chip, no_persistent_cache):
    """One jitted epoch of online training (what every engine dispatches)
    on 512² cross-field slices, under the lowering a TPU resolves for
    ``auto``.  Two one-slice steps keep the compile under a minute: the
    HIGHEST-precision tap GEMMs with 2–8 channels are what the TPU
    compiler spends its time on, and that grows with the batch."""
    net_cfg = skipping_dnn.SkippingDNNConfig(c_in=2)
    n, batch = 2, 1
    params = jax.eval_shape(
        lambda: skipping_dnn.init_params(jax.random.PRNGKey(0), net_cfg))
    opt = jax.eval_shape(adamw_init, params)
    put = lambda t: jax.tree.map(                              # noqa: E731
        lambda a: _sds(one_chip, a.shape, a.dtype), t)
    with dispatch.force_backend("tpu"):
        _, chosen = dispatch.resolve("dnn_forward", "auto")
        c = online_trainer._train_epoch.lower(
            put(params), put(opt),
            _sds(one_chip, (n, W, W, 2), jnp.float32),
            _sds(one_chip, (n, W, W, 1), jnp.float32),
            _sds(one_chip, (2,), jnp.uint32),
            _sds(one_chip, (), jnp.int32),
            cfg_reg=True, cfg_skip=True, batch=batch, steps=n // batch,
            total_steps=2 * n // batch, base_lr=1e-2, min_lr_frac=0.0,
            loss="mse", lowering="auto").compile()
    assert chosen == "jit"
    mem = c.memory_analysis()
    # Fits one v5e chip's 16 GB with room for the rest of the snapshot.
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 8 * 2**30
