"""The check that decides `correct`, driven through the whole harness on
the CPU at tiny sizes: sound runs pass, the control (the reference in
bfloat16) fails, and each fault a cell can have, planted under the timed
path, turns `correct` false."""
import dataclasses

import numpy as np
import pytest

from tiny import run_tiny

CELLS = ["ycsb-a.zipf099", "ycsb-c.zipf099", "pagerank.kron19",
         "ycsb-a.zipf099.4chip"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_passes_and_the_control_fails(workload):
    line = run_tiny(workload, control=True)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())
    assert any(c["value"] > c["limit"] for c in line["control"].values())
    assert line["attempted"] > 0 and line["failed"] == 0


# -- faults under the timed path --------------------------------------------
def state_unchanged(mp):
    """The write-back step returns the table as it was."""
    from repro.core.backend import JaxBackend, SpmdBackend

    for cls in (JaxBackend, SpmdBackend):
        mp.setattr(cls, "apply_writes", lambda self, *a, **k: None)


def half_batch(mp):
    """Half of each batch left out; its results are the mean of the rest."""
    from repro.kvstore import DistributedHashTable

    orig = DistributedHashTable.execute_batch

    def half(self, keys, is_read, operand, **kw):
        h = keys.size // 2
        res = orig(self, keys[:h], is_read[:h], operand[:h], **kw)
        rest = np.broadcast_to(res.values.mean(0),
                               (keys.size - h,) + res.values.shape[1:])
        return dataclasses.replace(
            res, values=np.concatenate([res.values, rest]))

    mp.setattr(DistributedHashTable, "execute_batch", half)


def answer_altered(mp):
    """Every GET result off by 1e-3 where the lambda produces it."""
    from repro.kvstore import hashtable

    orig = hashtable._muladd_lambda

    def altered(contexts, in_vals):
        out = orig(contexts, in_vals)
        return {**out, "result": out["result"] + 1e-3}

    mp.setattr(hashtable, "_muladd_lambda", altered)


def exchange_left_out(mp):
    """The mesh's all-to-alls leave every shard with its own buffer."""
    from repro.core import shardexec

    mp.setattr(shardexec, "_a2a", lambda x: x)


def pr_writeback_dropped(mp):
    """Each round's aggregated contributions are never applied."""
    from repro.graph import distedgemap

    orig = distedgemap.dist_edge_map

    def dropped(og, U, f, write_back, *a, **k):
        return orig(og, U, f, lambda vs, agg: np.ones(vs.size, bool), *a, **k)

    mp.setattr(distedgemap, "dist_edge_map", dropped)


def pr_half_edges(mp):
    """Half of the edge values left out, the mean taken over the rest."""
    from repro.core.backend import JaxBackend

    orig = JaxBackend.combine_by_key

    def half(self, values, keys, *a, **k):
        values = np.array(values, dtype=np.float64)
        h = values.shape[0] // 2
        values[h:] = values[:h].mean()
        return orig(self, values, keys, *a, **k)

    mp.setattr(JaxBackend, "combine_by_key", half)


def pr_rank_altered(mp):
    """Each vertex's summed contribution off by 0.1% where it is produced."""
    from repro.core.backend import JaxBackend

    orig = JaxBackend.combine_by_key

    def altered(self, *a, **k):
        uniq, combined = orig(self, *a, **k)
        return uniq, combined * 1.001

    mp.setattr(JaxBackend, "combine_by_key", altered)


FAULTS = [
    ("ycsb-a.zipf099", state_unchanged), ("ycsb-a.zipf099", half_batch),
    ("ycsb-a.zipf099", answer_altered),
    ("ycsb-c.zipf099", half_batch), ("ycsb-c.zipf099", answer_altered),
    ("ycsb-a.zipf099.4chip", state_unchanged),
    ("ycsb-a.zipf099.4chip", half_batch),
    ("ycsb-a.zipf099.4chip", answer_altered),
    ("ycsb-a.zipf099.4chip", exchange_left_out),
    ("pagerank.kron19", pr_writeback_dropped),
    ("pagerank.kron19", pr_half_edges),
    ("pagerank.kron19", pr_rank_altered),
]


@pytest.mark.parametrize("workload,fault", FAULTS,
                         ids=[f"{w}-{f.__name__}" for w, f in FAULTS])
def test_a_planted_fault_is_not_correct(workload, fault, monkeypatch):
    fault(monkeypatch)
    line = run_tiny(workload, seconds=0.3)
    assert not line["correct"], line["checks"]
