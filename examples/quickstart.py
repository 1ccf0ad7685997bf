"""Quickstart against the unified ``repro.api`` surface: declare an
IndexSpec, build, answer typed c^2-k-ANN searches, check the theoretical
guarantee, then snapshot and reload the index without a rebuild.

  PYTHONPATH=src python examples/quickstart.py
"""

import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "src")

import repro
from repro.api import IndexSpec, SearchRequest
from repro.launch import compile_cache


def main():
    compile_cache.enable()
    rng = np.random.default_rng(0)
    n, d, nq, k = 30000, 64, 32, 10

    # clustered synthetic vectors (image-descriptor-like)
    centers = rng.standard_normal((64, d)).astype(np.float32)
    data = centers[rng.integers(0, 64, n)] \
        + 0.2 * rng.standard_normal((n, d)).astype(np.float32)
    queries = data[rng.choice(n, nq, replace=False)] \
        + 0.05 * rng.standard_normal((nq, d)).astype(np.float32)

    # one declarative build config — paper parameters: K=4, L=16
    # (PDET recommendation, Sec. VI-C3), c=1.5
    spec = IndexSpec(kind="static", K=4, L=16, c=1.5, beta_override=0.1)
    params = spec.derive_params()
    print(f"spec: {spec.kind} K={spec.K} L={spec.L} c={spec.c} -> "
          f"eps={params.epsilon:.3f} beta={params.beta:.3f} "
          f"success_prob>={params.success_probability:.3f}")

    index = repro.api.build(jnp.asarray(data), jax.random.key(0), spec)
    print(f"index: {index.index_size_bytes() / 1e6:.1f} MB, "
          f"L={params.L} trees, {index.forest.n_leaves} leaves each, "
          f"n_points={index.n_points}")

    # typed per-request overrides; r_min=None uses the per-(index, k) cache
    res = index.search(jnp.asarray(queries), SearchRequest(k=k, M=12))
    print(f"search: engine={res.stats.engine} "
          f"r_min={res.stats.r_min:.3f} (cached={res.stats.r_min_cached})")

    # ground truth + quality
    d2 = ((queries[:, None, :] - data[None, :, :]) ** 2).sum(-1)
    gt = np.argsort(d2, 1)[:, :k]
    gt_d = np.sqrt(np.sort(d2, 1)[:, :k])
    ids = np.asarray(res.ids)
    recall = np.mean([len(set(ids[i]) & set(gt[i])) / k for i in range(nq)])
    ratio = float(np.mean(np.asarray(res.dists) / np.maximum(gt_d, 1e-9)))
    ok = np.all(np.asarray(res.dists) <= params.c ** 2 * gt_d + 1e-4, axis=1)
    print(f"recall@{k}: {recall:.3f}   overall ratio: {ratio:.4f}")
    print(f"c^2 guarantee held on {ok.mean() * 100:.1f}% of queries "
          f"(bound: >={params.success_probability * 100:.1f}%)")
    assert ok.mean() >= params.success_probability

    # snapshot persistence: a service restart skips the rebuild entirely
    with tempfile.TemporaryDirectory() as tmp:
        index.save(tmp)
        reloaded = repro.api.load(tmp)
        res2 = reloaded.search(jnp.asarray(queries), SearchRequest(k=k, M=12))
        assert np.array_equal(np.asarray(res.ids), np.asarray(res2.ids))
        assert np.array_equal(np.asarray(res.dists), np.asarray(res2.dists))
        print("snapshot: save -> load -> search is bit-identical "
              "(no rebuild)")


if __name__ == "__main__":
    main()
