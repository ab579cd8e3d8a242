"""AdamW math, schedules, compressed gradient sync."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.optim import (adamw_init, adamw_update, cosine_schedule,
                         warmup_cosine)
from repro.optim import grad_compress as GC


def test_adamw_matches_reference_math():
    p = {"w": jnp.asarray([[1.0, -2.0]], jnp.float32)}
    g = {"w": jnp.asarray([[0.5, 0.25]], jnp.float32)}
    st = adamw_init(p)
    p2, st2 = adamw_update(g, st, p, lr=0.1, b1=0.9, b2=0.999, eps=1e-8)
    m = 0.1 * np.array([[0.5, 0.25]])
    v = 0.001 * np.array([[0.25, 0.0625]])
    mhat, vhat = m / 0.1, v / 0.001
    expect = np.array([[1.0, -2.0]]) - 0.1 * mhat / (np.sqrt(vhat) + 1e-8)
    np.testing.assert_allclose(np.asarray(p2["w"]), expect, rtol=1e-5)
    assert int(st2.step) == 1


def test_grad_clip():
    p = {"w": jnp.ones((4,), jnp.float32)}
    g = {"w": jnp.full((4,), 100.0, jnp.float32)}
    st = adamw_init(p)
    p2, _ = adamw_update(g, st, p, lr=0.1, grad_clip_norm=1.0)
    assert np.isfinite(np.asarray(p2["w"])).all()


def test_schedules_endpoints():
    lr = cosine_schedule(1e-2, 100)
    assert abs(float(lr(jnp.asarray(0))) - 1e-2) < 1e-9
    assert float(lr(jnp.asarray(100))) < 1e-6
    wu = warmup_cosine(1e-3, 10, 100)
    assert float(wu(jnp.asarray(0))) == 0.0
    assert abs(float(wu(jnp.asarray(10))) - 1e-3) < 1e-9


def test_quantize_ef_error_feedback_accumulates():
    g = {"w": jnp.asarray(np.random.default_rng(0)
                          .standard_normal((64, 64)), jnp.float32)}
    ef = GC.init_ef(g)
    q, s, ef2 = GC.quantize_ef(g, ef, bits=8)
    deq = GC.dequantize(q, s)
    err = np.abs(np.asarray(deq["w"]) - np.asarray(g["w"])).max()
    scale = float(s["w"])
    assert err <= scale * 0.5 + 1e-7      # within half a quantization step
    # ef carries exactly the quantization error
    np.testing.assert_allclose(np.asarray(ef2["w"]),
                               np.asarray(g["w"]) - np.asarray(deq["w"]),
                               atol=1e-7)


def test_compressed_psum_under_shard_map():
    import jax
    from jax.sharding import PartitionSpec as P

    if jax.device_count() < 1:
        return
    mesh = jax.make_mesh((1,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    g = {"w": jnp.ones((8, 8), jnp.float32) * 0.5}
    ef = GC.init_ef(g)

    def f(g, ef):
        return GC.compressed_psum(g, ef, "pod", bits=8)

    out, ef2 = jax.shard_map(
        f, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        check_vma=False)(g, ef)
    np.testing.assert_allclose(np.asarray(out["w"]), 0.5, atol=0.01)


def test_neurlz_grad_archive_compresses():
    rng = np.random.default_rng(0)
    g = {"layers": {"w_in": jnp.asarray(
        np.cumsum(rng.standard_normal((64, 128)), 0), jnp.float32)}}
    rep = GC.neurlz_grad_archive(g, rel_eb=1e-3)
    assert rep["ratio"] > 1.5, rep["ratio"]
