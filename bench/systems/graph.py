"""Cells on graph analytics: PageRank through ``graph.algorithms.pagerank``
on one ``GraphSession(og, backend=...)`` over an ingested graph.

Set-up generates the configuration's Graph500 Kronecker graph from the
seed, ingests it (``graph.partition.ingest``) and runs the mix's warm-up
calls. One call of the window is one PageRank job of ``rounds_per_call``
iterations from the uniform start; the check compares the ranks of every
call against ``references/pagerank.py``.
"""
from __future__ import annotations

import time

import numpy as np

from harness.kronecker import graphalytics_graph


class System:
    def __init__(self, cfg: dict, mix: dict, seed: int):
        from repro.graph import Graph, GraphSession, ingest

        t0 = time.perf_counter()
        g = cfg["graph"]
        self.n, self.src, self.dst = graphalytics_graph(
            int(g["scale"]), int(g["edge_factor"]), float(g["a"]),
            float(g["b"]), float(g["c"]), seed)
        self.m = self.src.size
        self.alpha = float(mix["damping"])
        self.rounds = self.call_units = int(mix["rounds_per_call"])
        self.warm_calls = int(mix["warm_calls"])
        t1 = time.perf_counter()
        self.og = ingest(Graph(self.n, self.src, self.dst),
                         P=int(cfg["machines"]), seed=seed)
        self.sess = GraphSession(self.og, backend=cfg["backend"])
        self.setup_parts = {"graph": t1 - t0,
                            "ingest": time.perf_counter() - t1}
        self.rec = []  # ranks after each call of the window
        self.done_rounds = 0

    def _run(self):
        from repro.graph import pagerank

        return pagerank(self.og, alpha=self.alpha, tol=0.0,
                        max_iter=self.rounds, session=self.sess)

    def warm(self) -> None:
        for _ in range(self.warm_calls):
            self._run()

    def call(self, i: int):
        return self._run()

    def sync(self, out) -> None:
        """The ranks come back as a host array: nothing is pending."""

    def record(self, i: int, out) -> None:
        pr, info = out
        self.rec.append(pr)
        self.done_rounds += info.rounds

    def counters(self) -> dict:
        bk = self.sess.backend
        return {"host_syncs": bk.host_syncs, "host_stages": bk.host_stages,
                "rounds": self.done_rounds}

    def window_work(self, calls: int) -> dict:
        return {"edges": self.m, "rounds": self.done_rounds}

    def window_metrics(self, calls: int, seconds: float, lat_s) -> dict:
        return {"pr_edges_per_s": self.m * self.done_rounds / seconds}

    def collect(self) -> None:
        self.og = self.sess = None

    def check(self, control: bool):
        from references.pagerank import l1, pagerank

        want = pagerank(self.src, self.dst, self.n, self.alpha, self.rounds)
        worst = max((l1(pr, want) for pr in self.rec), default=float("inf"))
        ctl = None
        if control:
            ctl = {"rank_l1": l1(pagerank(self.src, self.dst, self.n,
                                          self.alpha, self.rounds,
                                          control=True), want)}
        return {"rank_l1": worst}, ctl
