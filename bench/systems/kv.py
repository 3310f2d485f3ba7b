"""Cells on the key-value store: YCSB batches through
``DistributedHashTable.execute_batch(..., engine="tdorch", backend=...)``.

Set-up makes the table on the device from the seed, block by block, loads
it through ``bulk_load`` and runs the mix's warm-up batches. One call of the
window is one batch; it ends when the results are on the host and the
write-back has been applied (``backend.sync``). The check compares, against
``references/ycsb.py``: every GET result of the window on a few columns
drawn from the seed, whole result rows of the followed keys, and the same
columns and rows of the device table (per-chip slabs on a mesh) and of the
host mirror ``ht.values`` after the window.
"""
from __future__ import annotations

import time

import numpy as np

from harness.compare import Worst
from harness.hashdata import device_rows_fn
from harness.ycsbgen import YCSBTraffic

BLOCK_ROWS = 1 << 18  # rows made on the device per block


def _first_hits(keys: np.ndarray, followed: np.ndarray) -> np.ndarray:
    """Positions of the first operation on each followed key in a batch."""
    idx = np.flatnonzero(followed[keys])
    _, first = np.unique(keys[idx], return_index=True)
    return idx[first]


class System:
    def __init__(self, cfg: dict, mix: dict, seed: int):
        from repro.kvstore import DistributedHashTable

        t0 = time.perf_counter()
        self.seed = seed
        self.K = K = int(cfg["records"])
        self.W = W = int(cfg["record_words"])
        if K * W >= 2 ** 32:
            raise ValueError("the table's word index must fit 32 bits")
        self.backend = cfg["backend"]
        self.traffic = tr = YCSBTraffic(mix, K, seed)
        chk = mix["check"]
        self.cols = np.sort(tr.rng.choice(W, int(chk["columns"]),
                                          replace=False))
        self.rows = tr.check_rows(int(chk["hot_rows"]), int(chk["zipf_rows"]),
                                  int(chk["uniform_rows"]))
        followed = np.zeros(K, dtype=bool)
        followed[self.rows] = True
        self.pos = [_first_hits(b.keys, followed) for b in tr.pool]
        t1 = time.perf_counter()
        self.ht = DistributedHashTable(K, int(cfg["machines"]), value_width=W)
        rows = min(BLOCK_ROWS, 1 << (K - 1).bit_length())
        block = device_rows_fn(seed, W, rows)
        for lo in range(0, K, rows):
            vals = np.asarray(block(np.uint32(lo)))[:min(rows, K - lo)]
            self.ht.bulk_load(np.arange(lo, lo + vals.shape[0]), vals)
        self.setup_parts = {"traffic": t1 - t0,
                            "table": time.perf_counter() - t1}
        self.sess = None
        self.call_units = tr.n  # operations in one call
        self.rec = []  # (pool index, results on cols, followed result rows)

    # -- the calls ----------------------------------------------------------
    def _run(self, b):
        return self.ht.execute_batch(b.keys, b.is_read, b.operand,
                                     engine="tdorch", backend=self.backend)

    def warm(self) -> None:
        for b in self.traffic.warm:
            self._run(b)
            self.sync(None)

    def call(self, i: int):
        return self._run(self.traffic.pool[i % len(self.traffic.pool)])

    def sync(self, out) -> None:
        if self.sess is None:
            self.sess = self.ht.session("tdorch", backend=self.backend)
        self.sess.backend.sync(self.ht.store)

    def record(self, i: int, out) -> None:
        j = i % len(self.traffic.pool)
        self.rec.append((j, out.values[:, self.cols], out.values[self.pos[j]]))

    # -- counters -----------------------------------------------------------
    def counters(self) -> dict:
        bk = self.sess.backend
        out = {"host_syncs": bk.host_syncs, "host_stages": bk.host_stages}
        stats = getattr(bk, "stage_stats", None)
        if stats is not None:
            out["work_ratios"] = [float(s.work_ratio()) for s in stats]
        return out

    def window_work(self, calls: int) -> dict:
        """What the window's batches asked of the table, for the roofline."""
        pool = self.traffic.pool
        writes = [np.unique(pool[i % len(pool)].keys[
            ~pool[i % len(pool)].is_read]).size for i in range(calls)]
        return {"ops": [self.traffic.n] * calls, "distinct_writes": writes,
                "row_bytes": 4 * self.W}

    def window_metrics(self, calls: int, seconds: float, lat_s) -> dict:
        return {"kv_ops_per_s": calls * self.call_units / seconds,
                "kv_batch_p95_ms": 1e3 * float(np.percentile(lat_s, 95))}

    # -- the check ----------------------------------------------------------
    def collect(self) -> None:
        """Read what the check compares out of the program, then free it."""
        store, bk = self.ht.store, self.sess.backend
        dv = bk.resident(store)
        if dv.ndim == 3:  # mesh: (machines, slab rows, width), one slab a chip
            lay = store.shard_layout()
            by_col = np.asarray(dv[:, :, self.cols])[lay.owner,
                                                     lay.local_slot]
            by_row = np.asarray(dv[lay.owner[self.rows],
                                   lay.local_slot[self.rows]])
        else:
            by_col = np.asarray(dv[:, self.cols])
            by_row = np.asarray(dv[self.rows])
        self.device_table = (by_col, by_row)
        self.mirror = (store.values[:, self.cols].copy(),
                       store.values[self.rows].copy())
        del dv
        self.ht = self.sess = None

    def check(self, control: bool):
        from references.ycsb import Replay

        def replay(dtype):
            return Replay(self.seed, self.K, self.W, self.cols, self.rows,
                          dtype)

        ref = replay(np.float64)
        ctl = None
        if control:
            import ml_dtypes
            ctl = replay(ml_dtypes.bfloat16)
        names = ("get_err", "table_err", "mirror_err")
        got = {k: Worst() for k in names}
        ctl_got = {k: Worst() for k in names}
        for b in self.traffic.warm:
            for r in (ref, ctl):
                if r is not None:
                    r.apply(b.keys, b.is_read, b.operand)
        pool = self.traffic.pool
        for j, res_cols, res_rows in self.rec:
            b = pool[j]
            want = ref.results(b.keys, self.pos[j])
            got["get_err"].add(res_cols, want[0])
            got["get_err"].add(res_rows, want[1])
            if ctl is not None:
                c = ctl.results(b.keys, self.pos[j])
                ctl_got["get_err"].add(c[0], want[0])
                ctl_got["get_err"].add(c[1], want[1])
                ctl.apply(b.keys, b.is_read, b.operand)
            ref.apply(b.keys, b.is_read, b.operand)
        for name, table in (("table_err", self.device_table),
                            ("mirror_err", self.mirror)):
            for g, w in zip(table, ref.table()):
                got[name].add(g, w)
            if ctl is not None:
                for g, w in zip(ctl.table(), ref.table()):
                    ctl_got[name].add(g, w)
        checks = {k: v.value for k, v in got.items()}
        return checks, ({k: v.value for k, v in ctl_got.items()}
                        if ctl is not None else None)
