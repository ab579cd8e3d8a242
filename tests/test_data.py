import numpy as np
import pytest

from repro.data import fields as F
from repro.data.tokens import TokenStream


def test_field_shapes_dtypes():
    for ds in ("nyx", "miranda", "hurricane"):
        flds = F.make_fields(ds, shape=(16, 16, 16), seed=0)
        assert set(flds) == set(F.DATASET_FIELDS[ds])
        for v in flds.values():
            assert v.shape == (16, 16, 16)
            assert str(v.dtype) == F.DATASET_DTYPES[ds]
            assert np.isfinite(v).all()


def test_cross_field_correlation_present():
    """The shared-latent construction must induce |corr| > 0.3 — that's the
    physics cross-field learning exploits."""
    flds = F.make_fields("nyx", shape=(24, 24, 24), seed=1, coupling=0.8)
    t = np.log(np.maximum(flds["temperature"].ravel(), 1e-9))
    d = np.log(np.maximum(flds["dark_matter_density"].ravel(), 1e-9))
    corr = np.corrcoef(t, d)[0, 1]
    assert abs(corr) > 0.3, corr


def test_coupling_zero_decorrelates():
    flds = F.make_fields("nyx", shape=(24, 24, 24), seed=1, coupling=0.0)
    t = np.log(np.maximum(flds["temperature"].ravel(), 1e-9))
    d = np.log(np.maximum(flds["dark_matter_density"].ravel(), 1e-9))
    assert abs(np.corrcoef(t, d)[0, 1]) < 0.3


@pytest.mark.parametrize("dataset", ["nyx", "miranda", "hurricane"])
def test_field_subset_is_the_same_bytes(dataset):
    # Generation stops after the last requested field; the fields come from
    # one RNG stream, so a prefix or a single field matches the full block.
    full = F.make_fields(dataset, shape=(6, 10, 9), seed=5)
    names = list(full)
    for k in range(1, len(names) + 1):
        sub = F.make_fields(dataset, shape=(6, 10, 9), seed=5,
                            names=names[:k])
        assert list(sub) == names[:k]
        assert all(sub[n].tobytes() == full[n].tobytes() for n in sub)
    last = F.make_fields(dataset, shape=(6, 10, 9), seed=5,
                         names=[names[-1]])
    assert list(last) == [names[-1]]
    assert last[names[-1]].tobytes() == full[names[-1]].tobytes()
    with pytest.raises(ValueError, match="no fields"):
        F.make_fields(dataset, shape=(6, 10, 9), names=["nope"])


def test_token_stream_deterministic_replay():
    s1 = TokenStream(1000, 4, 64, seed=7)
    a = [s1.next_batch() for _ in range(3)]
    state = s1.checkpoint()
    b = [s1.next_batch() for _ in range(2)]
    s2 = TokenStream(1000, 4, 64, seed=7)
    s2.restore(state)
    c = [s2.next_batch() for _ in range(2)]
    for x, y in zip(b, c):
        assert np.array_equal(x, y)
    s3 = TokenStream(1000, 4, 64, seed=7)
    for x in a:
        assert np.array_equal(x, s3.next_batch())


def test_token_stream_vocab_range():
    s = TokenStream(512, 2, 128, seed=0)
    t = s.next_batch()
    assert t.min() >= 0 and t.max() < 512
