"""The seeded generators: deterministic by seed, and the statistics their
configurations state."""
import numpy as np
import pytest

from bench.data import dense_ar1, seeds

DENSE = {"n": 3000, "p": 200, "rho": 0.6, "n_nonzero": 10, "snr": 5.0,
         "dtype": "float32"}
BIG = 2**40 + 3                       # more than 32 bits


def test_seed_streams_differ_and_repeat():
    assert np.array_equal(seeds.seed_words(BIG, "a"),
                          seeds.seed_words(BIG, "a"))
    assert not np.array_equal(seeds.seed_words(BIG, "a"),
                              seeds.seed_words(BIG, "b"))
    assert not np.array_equal(seeds.seed_words(BIG, "a"),
                              seeds.seed_words(BIG + 1, "a"))


def test_dense_deterministic_by_seed():
    X1, Y1 = dense_ar1.generate(DENSE, BIG, 2)
    X2, Y2 = dense_ar1.generate(DENSE, BIG, 2)
    X3, Y3 = dense_ar1.generate(DENSE, BIG + 1, 2)
    assert len(X1) == 2 and Y1.shape == (2, 3000)
    for a, b in zip(X1 + [Y1], X2 + [Y2]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(X1 + [Y1], X3 + [Y3]):
        assert not np.array_equal(np.asarray(a), np.asarray(b))
    # the designs of one run differ from each other
    assert not np.array_equal(np.asarray(X1[0]), np.asarray(X1[1]))


@pytest.mark.parametrize("k", [0, 1])
def test_dense_ar1_statistics(k):
    Xs, Y = dense_ar1.generate(DENSE, BIG, 2)
    X = np.asarray(Xs[k], np.float64)
    assert X.shape == (3000, 200)
    np.testing.assert_allclose(X.std(axis=0).mean(), 1.0, atol=0.02)
    lag = [np.corrcoef(X[:, j], X[:, j + d])[0, 1]
           for d in (1, 2) for j in range(199 - d)]
    half = len(lag) // 2 + 1
    assert np.mean(lag[:half]) == pytest.approx(0.6, abs=0.02)
    assert np.mean(lag[half:]) == pytest.approx(0.36, abs=0.02)
    # the block recurrence carries across block edges (128 columns)
    assert np.corrcoef(X[:, 127], X[:, 128])[0, 1] == pytest.approx(
        0.6, abs=0.06)


def test_dense_targets_have_the_stated_snr():
    Xs, Y = dense_ar1.generate(DENSE, BIG, 2)
    for X, y in zip(Xs, np.asarray(Y, np.float64)):
        X = np.asarray(X, np.float64)
        # the truth is n_nonzero ones: regress y on X to recover it
        b = np.linalg.lstsq(X, y, rcond=None)[0]
        support = np.flatnonzero(np.abs(b - 1.0) < 0.3)
        assert len(support) == DENSE["n_nonzero"]
        signal = X[:, support].sum(axis=1)
        assert np.linalg.norm(signal) / np.linalg.norm(y - signal) \
            == pytest.approx(DENSE["snr"], rel=1e-4)
