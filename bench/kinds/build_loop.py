"""Back-to-back static builds of the whole index, search layout included,
each from a fresh build key over the seed's data.

Mix parameters: ``check_queries`` (held-out queries the last forest
answers for the check).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import reference
from bench.traffic import (QueryLog, build_index, check_answers,
                           forest_arrays, index_spec, span)


class Traffic:
    def __init__(self, run, mix: dict):
        self.run = run
        self.mix = mix
        self.counters: dict = {}
        self.index = None
        self.key = None

    def _key(self, i: int):
        import jax
        return jax.random.fold_in(self.run.build_key, i)

    def setup(self) -> None:
        build_index(self.run.data, self._key(0), self.run.cfg)
        gc.collect()

    def window(self, seconds: float) -> dict:
        import jax
        import repro
        builds = 0
        t0 = time.perf_counter()
        while True:
            self.index = None
            gc.collect()
            self.key = self._key(builds + 1)
            with span("bench.build"):
                index = repro.api.build(self.run.data, self.key,
                                        index_spec(self.run.cfg))
                jax.block_until_ready(index.forest.point_ids)
            with span("bench.plan"):
                jax.block_until_ready(index.fused_plan().points_sorted)
            self.index = index
            builds += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        idx = self.run.cfg["index"]
        self.counters = {"builds": builds, "n": self.run.cfg["n"],
                         "K": idx["K"], "L": idx["L"]}
        return {"elapsed_s": elapsed, "attempted": builds, "failed": 0,
                "build_points_per_s": self.run.cfg["n"] * builds / elapsed}

    def release(self) -> None:
        pass

    def check(self) -> dict:
        """The last forest the window built, and its answers to
        ``check_queries`` held-out queries through the normal search."""
        import jax.numpy as jnp
        from repro.api import SearchRequest
        run, index = self.run, self.index
        out = reference.forest_numbers(run.data, self.key,
                                       **forest_arrays(index))
        q = np.arange(self.mix["check_queries"])
        res = index.search(jnp.asarray(run.queries_host[q]),
                           SearchRequest(k=run.k))
        log = QueryLog()
        log.add(q, np.asarray(res.ids), np.asarray(res.dists))
        self.index = None
        gc.collect()
        out.update(check_answers(run, log))
        return out
