import numpy as np

from harness.kronecker import graphalytics_graph, kronecker_edges


def test_raw_edge_count_and_label_range():
    s, d = kronecker_edges(10, 16, 0.57, 0.19, 0.19, np.random.default_rng(3))
    assert s.size == d.size == 16 * 1024
    assert s.min() >= 0 and max(s.max(), d.max()) < 1024


def test_cleanup_and_degree_skew():
    n, src, dst = graphalytics_graph(12, 16, 0.57, 0.19, 0.19, seed=5)
    assert n <= 4096 and src.size % 2 == 0
    assert src.size <= 2 * 16 * 4096
    assert not (src == dst).any()
    # symmetric and simple: every directed edge once, and its reverse too
    fwd = np.unique(src * n + dst)
    assert fwd.size == src.size
    assert np.array_equal(np.sort(fwd), np.sort(dst * n + src))
    # no isolated vertex is kept
    deg = np.bincount(src, minlength=n)
    assert (deg > 0).all()
    # skew: the busiest 1% of vertices hold far more than 1% of the edges
    top = np.sort(deg)[::-1][:max(n // 100, 1)].sum() / deg.sum()
    assert top > 0.08
    assert deg.max() > 20 * np.median(deg)


def test_same_seed_same_graph():
    a = graphalytics_graph(9, 16, 0.57, 0.19, 0.19, seed=2 ** 31 + 9)
    b = graphalytics_graph(9, 16, 0.57, 0.19, 0.19, seed=2 ** 31 + 9)
    assert a[0] == b[0] and np.array_equal(a[1], b[1])
