"""Per-kernel shape/dtype sweeps vs pure-jnp oracles (interpret mode on
CPU; tests/test_tpu_compile.py compiles the same kernels for the TPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(7)


@pytest.mark.parametrize("shape", [(8, 16, 16), (24, 40, 33), (16, 8, 128),
                                   (5, 30, 17)])
@pytest.mark.parametrize("eb", [0.1, 1e-3])
def test_lorenzo_fwd_matches_ref(shape, eb):
    x = np.cumsum(RNG.standard_normal(shape), axis=0).astype(np.float32)
    d, rec = ops.lorenzo_quantize(x, eb)
    d_ref, rec_ref = ref.lorenzo3d_fwd_ref(jnp.asarray(x), eb)
    assert np.array_equal(np.asarray(d), np.asarray(d_ref))
    assert np.allclose(np.asarray(rec), np.asarray(rec_ref))


@pytest.mark.parametrize("shape", [(8, 16, 16), (12, 24, 20)])
def test_lorenzo_inverse_roundtrip(shape):
    eb = 0.01
    x = np.cumsum(RNG.standard_normal(shape), axis=1).astype(np.float32)
    d, rec = ops.lorenzo_quantize(x, eb)
    q = ops.lorenzo_dequantize(d, eb)
    # inverse reproduces the fused-kernel reconstruction
    assert np.allclose(np.asarray(q), np.asarray(rec), atol=1e-6)
    assert np.abs(np.asarray(q) - x).max() <= eb * (1 + 1e-6)


@pytest.mark.parametrize("shape", [(4, 16, 16), (16, 40, 33)])
@pytest.mark.parametrize("mode", [(True, True), (True, False), (False, False)])
def test_fused_enhance_matches_ref(shape, mode):
    regulated, strict = mode
    eb = 0.05
    z = RNG.standard_normal(shape).astype(np.float32)
    dec = RNG.standard_normal(shape).astype(np.float32)
    orig = (dec + RNG.uniform(-eb, eb, shape)).astype(np.float32)
    out, mask = ops.enhance(z, dec, orig, eb, regulated=regulated, strict=strict)
    out_r, mask_r = ref.fused_enhance_ref(jnp.asarray(z), jnp.asarray(dec),
                                          jnp.asarray(orig), eb,
                                          regulated=regulated, strict=strict)
    # 1-ulp differences possible (sigmoid fusion); mask knife-edges likewise
    assert np.allclose(np.asarray(out), np.asarray(out_r), rtol=2e-5, atol=1e-6)
    assert (np.asarray(mask) != np.asarray(mask_r)).mean() < 1e-2


def test_fused_enhance_strict_bound():
    eb = 0.05
    shape = (8, 32, 32)
    z = RNG.standard_normal(shape).astype(np.float32) * 5
    dec = RNG.standard_normal(shape).astype(np.float32)
    orig = (dec + RNG.uniform(-eb, eb, shape)).astype(np.float32)
    out, _ = ops.enhance(z, dec, orig, eb, regulated=True, strict=True)
    assert np.abs(np.asarray(out) - orig).max() <= eb * (1 + 1e-5)


# ---------------------------------------------------------------------------
# Oracle parity matrix: every kernel x dtype x shape-class (x regulation
# mode where it applies), each cell checked against the ref.py oracle.
# "even" shapes hit the aligned fast path, "odd"/"ragged" (prime extents)
# force the wrappers' explicit pad/crop.
# ---------------------------------------------------------------------------

SHAPES3D = {"even": (8, 16, 16), "odd": (7, 15, 33), "ragged": (17, 9, 11)}
DTYPES = [np.float32, np.float64]


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "f64"])
@pytest.mark.parametrize("cls", sorted(SHAPES3D))
def test_parity_matrix_lorenzo(cls, dtype):
    shape = SHAPES3D[cls]
    x = np.cumsum(RNG.standard_normal(shape), axis=0).astype(dtype)
    d, rec = ops.lorenzo_quantize(x, 1e-2)
    assert d.shape == shape and rec.shape == shape
    x32 = jnp.asarray(x.astype(np.float32))   # kernel computes in fp32
    d_r, rec_r = ref.lorenzo3d_fwd_ref(x32, 1e-2)
    assert np.array_equal(np.asarray(d), np.asarray(d_r))
    assert np.allclose(np.asarray(rec), np.asarray(rec_r), atol=1e-6)
    q = ops.lorenzo_dequantize(d, 1e-2)
    q_r = ref.lorenzo3d_inv_ref(d_r).astype(jnp.float32) * (2.0 * 1e-2)
    assert q.shape == shape
    assert np.allclose(np.asarray(q), np.asarray(q_r), atol=1e-6)


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "f64"])
@pytest.mark.parametrize("cls", sorted(SHAPES3D))
@pytest.mark.parametrize("strict", [True, False], ids=["strict", "relaxed"])
def test_parity_matrix_enhance(cls, dtype, strict):
    shape = SHAPES3D[cls]
    eb = 0.05
    z = RNG.standard_normal(shape).astype(np.float32)
    dec = RNG.standard_normal(shape).astype(dtype)
    orig = (dec + RNG.uniform(-eb, eb, shape)).astype(dtype)
    out, mask = ops.enhance(z, dec, orig, eb, regulated=True, strict=strict)
    assert out.shape == shape and mask.shape == shape
    out_r, mask_r = ref.fused_enhance_ref(jnp.asarray(z), jnp.asarray(dec),
                                          jnp.asarray(orig), eb,
                                          regulated=True, strict=strict)
    assert np.allclose(np.asarray(out), np.asarray(out_r),
                       rtol=2e-5, atol=1e-6)
    assert (np.asarray(mask) != np.asarray(mask_r)).mean() < 1e-2


# ---------------------------------------------------------------------------
# Pad/crop regressions: non-multiple shapes must engage a real tile (not
# degrade to tile=1) and crop back exactly.
# ---------------------------------------------------------------------------

def test_pick_tz_ragged_depth_uses_real_tile():
    # Prime depth: before the pad/crop fix this degraded to tz=1 (one grid
    # step per plane); now the largest fitting slab is chosen and the depth
    # is padded up to it.
    assert ops.fwd_tz(17, 16, 16) > 1
    assert ops.inv_tz(17, 16, 16) > 1
    assert ops.fwd_tz(1, 16, 16) == 1   # never exceeds the depth
    # 512² planes: the double-buffered blocks of the largest slab must fit
    # the scoped-VMEM budget (tests/test_tpu_compile.py compiles these).
    assert ops.fwd_tz(512, 512, 512) == 2
    assert ops.inv_tz(512, 512, 512) == 2


def test_lorenzo_pad_crop_regression():
    eb = 1e-2
    x = np.cumsum(RNG.standard_normal((17, 9, 11)), axis=0).astype(np.float32)
    d, rec = ops.lorenzo_quantize(x, eb)
    # aligned reference computation: pad manually to the tile, crop after
    d_a, rec_a = ref.lorenzo3d_fwd_ref(jnp.asarray(x), eb)
    assert np.array_equal(np.asarray(d), np.asarray(d_a))
    assert np.array_equal(np.asarray(rec), np.asarray(rec_a))
    q = ops.lorenzo_dequantize(d, eb)
    assert q.shape == x.shape
    assert np.abs(np.asarray(q) - x).max() <= eb * (1 + 1e-6)


def test_enhance_pad_crop_regression():
    eb = 0.02
    shape = (7, 13, 5)   # rows = 91 (prime-ish): engages the row pad
    z = RNG.standard_normal(shape).astype(np.float32)
    dec = RNG.standard_normal(shape).astype(np.float32)
    orig = (dec + RNG.uniform(-eb, eb, shape)).astype(np.float32)
    out, mask = ops.enhance(z, dec, orig, eb)
    out_r, mask_r = ref.fused_enhance_ref(jnp.asarray(z), jnp.asarray(dec),
                                          jnp.asarray(orig), eb)
    assert out.shape == mask.shape == shape
    assert np.allclose(np.asarray(out), np.asarray(out_r),
                       rtol=2e-5, atol=1e-6)
    assert np.array_equal(np.asarray(mask), np.asarray(mask_r))


@pytest.mark.parametrize("shape", [(3, 8, 128), (5, 13, 200), (2, 40, 7)])
def test_lorenzo_inverse_wraps_like_cumsum(shape):
    # Codes near the int32 range: the in-kernel roll-and-add scans must wrap
    # exactly like jnp.cumsum (int32 addition is associative mod 2**32).
    d = RNG.integers(-2**31, 2**31 - 1, size=shape, dtype=np.int64
                     ).astype(np.int32)
    q = ops.lorenzo_dequantize(d, 0.5)          # step 1.0: q is the code
    q_r = ref.lorenzo3d_inv_ref(jnp.asarray(d)).astype(jnp.float32)
    assert np.array_equal(np.asarray(q), np.asarray(q_r))
