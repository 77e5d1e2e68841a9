"""The plain reference against NumPy, and the comparison numbers."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, data
from bench import reference as R


def _np_knn(x, q, k):
    d = ((q[:, None, :].astype(np.float64) - x[None, :, :]) ** 2).sum(-1)
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return ids, np.take_along_axis(d, ids, 1)


@pytest.fixture(scope="module")
def corpus():
    cfg = {"n": 2000, "d": 32,
           "data": {"latent_dim": 8, "points_per_cluster": 200,
                    "cluster_std": 0.15}}
    x, q = data.corpus(cfg, 3, 300)
    return x, np.asarray(q)


def test_knn_matches_numpy(corpus):
    x, q = corpus
    ids, d = R.knn(x, q, 10, chunk=128)        # 300 rows: a ragged chunk
    want_ids, want_d = _np_knn(np.asarray(x), q, 10)
    assert ids.shape == (300, 10)
    assert check.recall(ids, want_ids) > 0.999
    np.testing.assert_allclose(d, want_d, rtol=1e-4, atol=1e-4)


def test_pair_sqdist_matches_numpy(corpus):
    x, q = corpus
    xn = np.asarray(x)
    a, b = np.arange(50), np.arange(50, 100)
    np.testing.assert_allclose(R.pair_sqdist(x, a, b),
                               ((xn[a] - xn[b]) ** 2).sum(1), rtol=1e-6)
    np.testing.assert_allclose(R.pair_sqdist(x, q[:50], b),
                               ((q[:50] - xn[b]) ** 2).sum(1), rtol=1e-6)


def test_bfloat16_reference_is_the_lower_precision(corpus):
    x, q = corpus
    ids, d = R.knn(x, q, 10)
    ids16, d16 = R.knn(x, q, 10, precision="bfloat16")
    assert check.recall(ids16, ids) < 1.0
    # the float32 matmul form loses a little to cancellation; bfloat16
    # inputs lose far more
    err32 = check.answers(x, q, ids, d, 10, ids)["dist_err"]
    err16 = check.answers(x, q, ids16, d16, 10, ids)["dist_err"]
    assert err32 < 1e-3 and err16 > 10 * err32


def test_bad_rows():
    ids = np.array([[0, 1, 2], [0, 0, 1], [5, -1, 1], [1, 2, -1]])
    assert check.bad_rows(ids, n=5) == 2            # a repeat, an id >= n
    assert check.bad_rows(ids, n=5, k=3) == 3       # and a short row
    assert check.bad_rows(np.array([[1, 2]]), 5, owners=[2]) == 1


def test_verdict_holds_recall_from_below_and_gaps_from_above():
    ok, out = check.verdict({"recall_at_10": 0.95, "dist_err": 1e-6},
                            {"recall_at_10": 0.9, "dist_err": 1e-4})
    assert ok and out["dist_err"] == {"value": 1e-6, "limit": 1e-4}
    assert not check.verdict({"recall_at_10": 0.85}, {"recall_at_10": 0.9})[0]
    assert not check.verdict({"bad_ids": 1}, {"bad_ids": 0})[0]


def test_seeds_past_32_bits_make_distinct_data():
    k1, k2 = data.seed_key(2**33 + 1), data.seed_key(1)
    assert not bool(jnp.all(k1 == k2))
    with pytest.raises(ValueError):
        data.seed_key(-1)
