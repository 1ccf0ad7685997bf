"""Open loop: Poisson arrivals of single queries at ``rate_qps`` into a
``ServingRuntime(max_batch, pad_to)``.

Each request is submitted with its due time as its arrival, so its latency
counts from when it was due, whatever the generator's lateness.  The gaps
are one fixed draw (``arrival_seed``) shuffled by the run's seed: every
seed offers the same load in another order.

Mix parameters: ``rate_qps``, ``max_batch``, ``pad_to``, ``arrival_seed``.
"""

from __future__ import annotations

import time

import numpy as np

from bench.traffic import QueryLog, build_index, check_answers, span


def poisson_offsets(rate: float, seconds: float, arrival_seed: int,
                    run_seed: int) -> np.ndarray:
    """Due times (s from the window's start) of round(rate * seconds)
    requests: one fixed draw of exponential gaps, scaled to end inside the
    window and shuffled by the run's seed."""
    count = max(1, int(round(rate * seconds)))
    gaps = np.random.default_rng(arrival_seed).exponential(1.0, count + 1)
    gaps *= seconds / gaps.sum()
    rng = np.random.default_rng(run_seed % 2 ** 63)
    return np.cumsum(rng.permutation(gaps))[:count]


class Traffic:
    def __init__(self, run, mix: dict):
        self.run = run
        self.mix = mix
        self.log = QueryLog()
        self.counters: dict = {}

    def runtime(self):
        """A fresh serving runtime over the built index, as the mix sets
        it up."""
        from repro.api import SearchRequest
        from repro.serving import ServingRuntime
        k = self.run.k
        return ServingRuntime(self.index, k=k,
                              max_batch=self.mix["max_batch"],
                              pad_to=self.mix["pad_to"],
                              request=SearchRequest(k=k))

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.api import SearchRequest
        run, mix = self.run, self.mix
        self.index = build_index(run.data, run.build_key, run.cfg)
        probes = run.queries_host[:mix["max_batch"]]
        self.index.r_min_for(run.k, jnp.asarray(probes))
        self.rt = self.runtime()
        # one search per pad bucket, with the request the runtime makes
        for size in sorted({self.rt.batcher.bucket(s) for s in
                            range(1, mix["max_batch"] + 1)}):
            res = self.index.search(
                jnp.asarray(run.queries_host[:size]),
                SearchRequest(k=run.k, n_active=size))
            jax.block_until_ready(res.dists)
            np.asarray(res.ids)
        # The search slices a partial batch to its n_active rows before the
        # cached r_min lookup: one small program per partial size.
        d = run.queries_host.shape[1]
        for size in range(1, mix["max_batch"] + 1):
            pad = jnp.zeros((self.rt.batcher.bucket(size), d), jnp.float32)
            jax.block_until_ready(pad[:size])
        rng = np.random.default_rng(run.seed % 2 ** 63)
        self.order = rng.permutation(len(run.queries_host))

    def window(self, seconds: float) -> dict:
        from repro.serving import Answer
        rt, run = self.rt, self.run
        offsets = poisson_offsets(self.mix["rate_qps"], seconds,
                                  self.mix["arrival_seed"], run.seed)
        qidx = self.order[np.arange(len(offsets)) % len(self.order)]
        rids, late = [], np.zeros(len(offsets))
        t0 = time.perf_counter()
        due = t0 + offsets
        i = 0
        while i < len(due):
            now = time.perf_counter()
            with span("bench.submit"):
                while i < len(due) and due[i] <= now:
                    rids.append(rt.submit(run.queries_host[qidx[i]],
                                          arrival=due[i]))
                    late[i] = now - due[i]
                    i += 1
            with span("bench.pump"):
                ran = rt.pump()
            if not ran and i < len(due):
                time.sleep(max(0.0, min(due[i] - time.perf_counter(),
                                        rt.batcher.max_wait)))
        with span("bench.pump"):
            rt.flush()
        elapsed = time.perf_counter() - t0
        lat = []
        failed = 0
        for rid, q in zip(rids, qidx):
            out = rt.outcomes.get(rid)
            if isinstance(out, Answer):
                lat.append(out.latency_ms)
                self.log.add([q], out.ids[None], out.dists[None])
            else:
                failed += 1
        s = rt.stats
        self.counters = {"queries": s.queries, "pad_queries": s.pad_queries,
                         "batches": s.batches}
        return {"elapsed_s": elapsed, "attempted": len(rids),
                "failed": failed,
                "p99_ms": float(np.percentile(lat, 99)) if lat else None,
                "p50_ms": float(np.percentile(lat, 50)) if lat else None,
                "late_p99_ms": float(np.percentile(late, 99) * 1e3),
                "late_max_ms": float(late.max() * 1e3),
                "served_qps": len(lat) / elapsed}

    def release(self) -> None:
        self.rt = None
        self.index = None

    def check(self) -> dict:
        return check_answers(self.run, self.log)
