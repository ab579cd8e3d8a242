"""The device snapshot generator: seeded, and statistically the host
generator of ``src/repro/data/fields.py``."""
import numpy as np
import pytest

from _tiny import ROOT  # noqa: F401  (puts the repo on sys.path)


@pytest.fixture(scope="module")
def gen():
    import repro.core  # noqa: F401  (the harness runs under x64)
    from nlzbench import fields
    return fields


def _logcorr(a, b):
    return float(np.corrcoef(np.log(a).ravel(), np.log(b).ravel())[0, 1])


def test_same_seed_same_bytes_other_index_other_bytes(gen):
    names = ["temperature", "dark_matter_density"]
    seed = 2 ** 33 + 17          # wider than 32 bits
    a = gen.snapshot("nyx", (8, 16, 16), names, seed, index=3)
    b = gen.snapshot("nyx", (8, 16, 16), names, seed, index=3)
    c = gen.snapshot("nyx", (8, 16, 16), names, seed, index=4)
    d = gen.snapshot("nyx", (8, 16, 16), names, seed + 2 ** 32, index=3)
    for n in names:
        assert a[n].dtype == np.float32 and a[n].shape == (8, 16, 16)
        assert a[n].tobytes() == b[n].tobytes()
        assert a[n].tobytes() != c[n].tobytes()
        assert a[n].tobytes() != d[n].tobytes()


def test_fields_must_be_a_prefix(gen):
    with pytest.raises(ValueError, match="prefix"):
        gen.snapshot("nyx", (4, 8, 8), ["dark_matter_density"], 1)


def test_nyx_cross_field_correlation_matches_host_generator(gen):
    from repro.data import fields as host
    names = ["temperature", "dark_matter_density"]
    dev = [_logcorr(*gen.snapshot("nyx", (32, 32, 32), names, s).values())
           for s in range(4)]
    ref = []
    for s in range(4):
        f = host.make_fields("nyx", (32, 32, 32), seed=s, names=names)
        ref.append(_logcorr(f[names[0]], f[names[1]]))
    assert np.mean(dev) == pytest.approx(np.mean(ref), abs=0.05)


def test_hurricane_sparsity_matches_host_generator(gen):
    from repro.data import fields as host
    names = ["cloud", "precip", "w"]
    dev = gen.snapshot("hurricane", (16, 32, 32), names, 5)
    ref = host.make_fields("hurricane", (16, 32, 32), seed=5)
    for n in ("cloud", "precip"):
        assert (dev[n] == 0).mean() == pytest.approx((ref[n] == 0).mean(),
                                                     abs=0.05)
    assert abs(float(dev["w"].mean())) < 2.0 and dev["w"].std() > 4.0


def test_evolving_stream_moves_every_point_and_keeps_the_statistics(gen):
    names = ["temperature", "dark_matter_density"]
    seed, step = 2 ** 33 + 5, 0.05
    snaps = [gen.snapshot("nyx", (16, 32, 32), names, seed, index=k,
                          step=step) for k in range(4)]
    again = gen.snapshot("nyx", (16, 32, 32), names, seed, index=2, step=step)
    for n in names:
        assert again[n].tobytes() == snaps[2][n].tobytes()
        logs = [np.log(s[n]).ravel() for s in snaps]
        for a, b in zip(logs, logs[1:]):
            assert (a != b).mean() > 0.99          # a new input at each step
            assert np.corrcoef(a, b)[0, 1] > 0.99  # one step of evolution
        sd = [x.std() for x in logs]
        assert max(sd) / min(sd) < 1.05
