"""Each plain reference against the repository's numpy backend, tiny size."""
import ml_dtypes
import numpy as np

from harness.hashdata import device_rows_fn, values_np
from harness.ycsbgen import YCSBTraffic
from references.pagerank import l1, pagerank
from references.ycsb import Replay


def test_table_values_device_and_host_agree():
    rows = device_rows_fn(2 ** 31 + 5, 250, 64)
    got = np.asarray(rows(np.uint32(128)))
    want = values_np(2 ** 31 + 5, np.arange(128, 192), np.arange(250), 250)
    assert np.array_equal(got, want)
    assert want.min() >= -1 and want.max() < 1


def test_ycsb_replay_matches_the_numpy_backend():
    from repro.kvstore import DistributedHashTable

    K, W, seed = 512, 250, 11
    mix = {"zipf_constant": 0.99, "ops_per_batch": 256, "read_fraction": 0.5,
           "warm_batches": 0, "pool_batches": 5}
    tr = YCSBTraffic(mix, K, seed)
    ht = DistributedHashTable(K, 4, value_width=W)
    ht.bulk_load(np.arange(K), values_np(seed, np.arange(K), np.arange(W), W))
    cols, rows = np.array([0, 7, 249]), np.array([1, 5, 300])
    ref = Replay(seed, K, W, cols, rows)
    for b in tr.pool:
        pos = np.flatnonzero(np.isin(b.keys, rows))
        want_c, want_r = ref.results(b.keys, pos)
        res = ht.execute_batch(b.keys, b.is_read, b.operand, engine="tdorch",
                               backend="numpy")
        assert np.array_equal(res.values[:, cols], want_c)
        assert np.array_equal(res.values[pos], want_r)
        ref.apply(b.keys, b.is_read, b.operand)
    by_col, by_row = ref.table()
    assert np.array_equal(ht.values[:, cols], by_col)
    assert np.array_equal(ht.values[rows], by_row)


def test_ycsb_control_rounds_to_bfloat16():
    ctl = Replay(3, 64, 8, np.arange(8), np.arange(4), ml_dtypes.bfloat16)
    ref = Replay(3, 64, 8, np.arange(8), np.arange(4))
    gap = np.abs(ctl.by_col.astype(np.float64) - ref.by_col).max()
    assert 0 < gap <= 2 ** -8


def test_pagerank_reference_matches_the_numpy_backend():
    from repro.graph import Graph, GraphSession, ingest
    from repro.graph import pagerank as program_pagerank
    from harness.kronecker import graphalytics_graph

    n, src, dst = graphalytics_graph(9, 16, 0.57, 0.19, 0.19, seed=4)
    og = ingest(Graph(n, src, dst), P=4)
    got, info = program_pagerank(og, alpha=0.85, tol=0.0, max_iter=3,
                                 session=GraphSession(og, backend="numpy"))
    want = pagerank(src, dst, n, 0.85, 3)
    assert info.rounds == 3
    assert l1(got, want) < 1e-12
    assert abs(want.sum() - 1.0) < 1e-12
