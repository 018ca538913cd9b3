"""Synthetic corpus calibration, windowing correctness, K-means invariants."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import clustering
from repro.data import partition, synthetic, windows


def test_series_deterministic():
    a = synthetic.generate_buildings("CA", [5, 7], days=10)
    b = synthetic.generate_buildings("CA", [5, 7], days=10)
    np.testing.assert_array_equal(a, b)
    c = synthetic.generate_buildings("FLO", [5], days=10)
    assert not np.allclose(a[0], c[0])


def test_corpus_calibration_matches_paper_marginals():
    """§4.1 / Fig. 2: min 0.16, Q1 4.7, median 12.7, Q3 28.4 kWh (±tol)."""
    means = synthetic.mean_consumption("CA", list(range(3000)))
    q1, med, q3 = np.percentile(means, [25, 50, 75])
    assert 8.0 < med < 18.0, med                  # paper: 12.7
    assert 3.0 < q1 < 8.0, q1                     # paper: 4.7
    assert 18.0 < q3 < 42.0, q3                   # paper: 28.4
    assert means.min() >= synthetic.MIN_KWH
    assert (means > 63.8).mean() > 0.02           # long tail beyond violin max


def test_series_shape_and_positivity():
    s = synthetic.generate_buildings("RI", [0], days=365)
    assert s.shape == (1, 35040)                  # paper: samples/building
    assert (s > 0).all()


def test_make_windows_alignment():
    series = np.arange(20, dtype=np.float32)
    x, y = windows.make_windows(series, lookback=4, horizon=2)
    assert x.shape == (15, 4, 1) and y.shape == (15, 2)
    np.testing.assert_array_equal(x[0, :, 0], [0, 1, 2, 3])
    np.testing.assert_array_equal(y[0], [4, 5])
    np.testing.assert_array_equal(x[-1, :, 0], [14, 15, 16, 17])
    np.testing.assert_array_equal(y[-1], [18, 19])


def test_minmax_roundtrip():
    r = np.random.default_rng(0)
    s = r.normal(size=(3, 100)).astype(np.float32) * 5 + 10
    n, stats = windows.minmax_normalize(s)
    assert n.min() >= 0 and n.max() <= 1
    np.testing.assert_allclose(windows.denormalize(n, stats), s, rtol=1e-5)


def test_daily_average_vector():
    s = synthetic.generate_buildings("CA", [1], days=30)
    z = windows.daily_average_vector(s, days=20)
    assert z.shape == (1, 20)
    np.testing.assert_allclose(z[0, 0], s[0, :96].mean(), rtol=1e-5)


def test_train_test_split_chronological():
    s = np.arange(100, dtype=np.float32)
    tr, te = windows.train_test_split(s, 0.75)
    assert len(tr) == 75 and len(te) == 25
    assert tr[-1] < te[0]


# ------------------------------------------------------------- K-means
@given(st.integers(0, 10_000), st.integers(2, 5))
@settings(max_examples=10, deadline=None)
def test_kmeans_assignment_is_nearest_centroid(seed, k):
    r = np.random.default_rng(seed)
    x = r.normal(size=(40, 8))
    cents, assign, inertia = clustering.kmeans(x, k, seed=seed)
    d2 = ((x[:, None, :] - cents[None]) ** 2).sum(-1)
    np.testing.assert_array_equal(assign, d2.argmin(1))
    assert inertia >= 0


def test_kmeans_separated_clusters():
    r = np.random.default_rng(0)
    x = np.concatenate([r.normal(size=(20, 4)) + 10,
                        r.normal(size=(20, 4)) - 10])
    _, assign, _ = clustering.kmeans(x, 2, seed=0)
    assert len(set(assign[:20])) == 1 and len(set(assign[20:])) == 1
    assert assign[0] != assign[-1]
    sil = clustering.silhouette_score(x, assign)
    assert sil > 0.8


def test_elbow_curve_monotone():
    r = np.random.default_rng(1)
    x = r.normal(size=(60, 6))
    inertias = clustering.elbow_curve(x, [1, 2, 4, 8], seed=0)
    assert (np.diff(inertias) <= 1e-6).all()      # inertia non-increasing in k


def test_assign_heldout():
    cents = np.array([[0.0, 0.0], [10.0, 10.0]])
    x = np.array([[1.0, 1.0], [9.0, 9.0]])
    np.testing.assert_array_equal(clustering.assign(x, cents), [0, 1])


# ------------------------------------------------------------- partition
def test_sample_clients_no_replacement():
    r = np.random.default_rng(0)
    s = partition.sample_clients(r, 100, 30)
    assert len(np.unique(s)) == 30


def test_local_steps_matches_epochs():
    assert partition.local_steps(100, 32, 1) == 4     # ceil(100/32)
    assert partition.local_steps(100, 32, 3) == 12
    assert partition.local_steps(1, 64, 2) == 2
