"""Closed loop of batches: one client sends ``batch`` queries through
``AnnIndex.search`` and waits for the answers, then sends the next batch;
the queries cycle through the seed's set.

Mix parameters: ``batch``.
"""

from __future__ import annotations

import time

import numpy as np

from bench.traffic import QueryLog, build_index, check_answers, span


class Traffic:
    def __init__(self, run, mix: dict):
        self.run = run
        self.batch = mix["batch"]
        self.log = QueryLog()
        self.counters: dict = {}

    def _batch(self, i: int) -> np.ndarray:
        nq = len(self.run.queries_host)
        return (np.arange(self.batch) + i * self.batch) % nq

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.api import SearchRequest
        run = self.run
        self.index = build_index(run.data, run.build_key, run.cfg)
        probes = run.queries_host[self._batch(0)]
        self.index.r_min_for(run.k, jnp.asarray(probes))
        self.request = SearchRequest(k=run.k)
        res = self.index.search(jnp.asarray(probes), self.request)
        jax.block_until_ready(res.dists)
        np.asarray(res.stats.rounds)
        np.asarray(res.stats.n_candidates)

    def window(self, seconds: float) -> dict:
        import jax.numpy as jnp
        rounds, cands = [], []
        i = 0
        t0 = time.perf_counter()
        while True:
            q = self._batch(i)
            with span("bench.search"):
                res = self.index.search(jnp.asarray(self.run.queries_host[q]),
                                        self.request)
            with span("bench.readback"):
                ids = np.asarray(res.ids)
                dists = np.asarray(res.dists)
                rounds.append(np.asarray(res.stats.rounds))
                cands.append(np.asarray(res.stats.n_candidates))
            self.log.add(q, ids, dists)
            i += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        self.counters = {"batches": i, "queries": i * self.batch,
                         "rounds": np.concatenate(rounds),
                         "n_candidates": cands, "d": self.run.cfg["d"]}
        return {"elapsed_s": elapsed, "attempted": i * self.batch,
                "failed": 0, "qps": i * self.batch / elapsed}

    def release(self) -> None:
        self.index = None

    def check(self) -> dict:
        return check_answers(self.run, self.log)
