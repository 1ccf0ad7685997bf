"""On-chip smoke test of the DET-LSH main path.

    python chip_smoke.py               # one TPU chip
    python chip_smoke.py --four-chips  # only the sharded PDET phase, 4 chips

One chip runs, in one process and in this order:

  1. device check — a platform other than ``tpu`` exits non-zero, naming
     what JAX found (there is no CPU fallback);
  2. the compiled fused ``range_rerank`` kernel against its XLA oracle
     (``kernels.ref``) and a float64 host computation;
  3. a static build at SIFT1M shape (ann-benchmarks sift-128-euclidean:
     1,000,000 x 128, L2) of clustered data made from a seed;
  4. 256 queries near the data, served in batches of 64 through
     ``ServingRuntime`` on the ``fused`` engine, checked against an exact
     top-10 and the c^2 guarantee bound;
  5. a streaming index on the same base: upserts past ``delta_capacity``
     (a seal, so the fused project->encode->pack kernel runs), deletes,
     then search.

``--four-chips`` builds the same index on a 4-device ``PlacementSpec`` and
checks that the ``pdet`` engine's answers are bit-identical to the
single-device ``fused`` engine's.  Every check raises on failure.  The last
stdout line is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
The persistent compile cache is on (``repro.launch.compile_cache``).
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

import numpy as np

SRC = pathlib.Path(__file__).resolve().parent / "src"

N, D = 1_000_000, 128            # SIFT1M shape
N_CLUSTERS = 1000
SPEC = dict(K=4, L=8, c=1.5, leaf_size=64)
K_NN = 10
N_QUERIES, BATCH = 256, 64
RECALL_FLOOR = 0.9


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def peak_gb(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "n/a" if peak is None else f"{peak / 1e9:.3f} GB"


# ---------------------------------------------------------------------------
# Data (made on the device from a seed)
# ---------------------------------------------------------------------------

def make_data(key, n: int, d: int, n_clusters: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gen(key):
        kc, ka, kn = jax.random.split(key, 3)
        centers = jax.random.normal(kc, (n_clusters, d), jnp.float32)
        assign = jax.random.randint(ka, (n,), 0, n_clusters)
        return centers[assign] + 0.3 * jax.random.normal(kn, (n, d),
                                                         jnp.float32)
    return gen(key)


def near(key, data, m: int, scale: float = 0.05):
    """m vectors drawn near random data rows."""
    import jax
    kr, kn = jax.random.split(key)
    rows = jax.random.randint(kr, (m,), 0, data.shape[0])
    return data[rows] + scale * jax.random.normal(kn, (m, data.shape[1]))


def exact_topk(data, queries, k: int, chunk: int = 64):
    """Exact L2 top-k on the device: f32 HIGHEST matmul + top_k, computed
    independently of the index code.  Returns numpy (ids, dists)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def one(q, data):
        d2 = (jnp.sum(q * q, 1, keepdims=True)
              - 2.0 * jnp.dot(q, data.T, precision=jax.lax.Precision.HIGHEST)
              + jnp.sum(data * data, 1)[None, :])
        neg, ids = jax.lax.top_k(-d2, k)
        return ids, jnp.sqrt(jnp.maximum(-neg, 0.0))

    outs = [one(queries[i:i + chunk], data)
            for i in range(0, len(queries), chunk)]
    return (np.concatenate([np.asarray(o[0]) for o in outs]),
            np.concatenate([np.asarray(o[1]) for o in outs]))


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_kernel(seed: int, *, L=8, B=64, K=4, nl=1003, ls=64, d=128,
                 E=257) -> None:
    """Compiled range_rerank vs the XLA oracle vs float64 on the host."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
    qp = jnp.asarray(rng.standard_normal((L, B, K)), jnp.float32)
    bp = jnp.sort(jnp.asarray(3 * rng.standard_normal((L, K, E)),
                              jnp.float32), axis=2, stable=True)
    lo = jnp.asarray(rng.integers(0, E - 1, (L, nl, K)), jnp.int16)
    hi = jnp.clip(lo + jnp.asarray(rng.integers(0, 4, (L, nl, K)),
                                   jnp.int16), 0, E - 2)
    lv = jnp.asarray(rng.random((L, nl)) > 0.05)
    pts = jnp.asarray(rng.standard_normal((L, nl * ls, d)), jnp.float32)
    pv = jnp.asarray(rng.random((L, nl * ls)) > 0.05)
    live = jnp.asarray(rng.random((L, nl * ls)) > 0.1)
    # radii at each lane's 10th-percentile leaf LB; lane 0 is done (-1)
    lb = ref.forest_leaf_lb(qp, lo.astype(jnp.int32), hi.astype(jnp.int32),
                            lv, bp)
    lbf = jnp.where(jnp.isfinite(lb), lb, jnp.nan).transpose(1, 0, 2)
    r = jnp.nanquantile(lbf.reshape(B, -1), 0.1, axis=1).at[0].set(-1.0)

    args = (q, qp, r, lo, hi, lv, bp, pts, pv, live)
    got = np.asarray(ops.range_rerank(*args, leaf_size=ls))
    want = np.asarray(jax.jit(lambda *a: ref.range_rerank(
        *a, leaf_size=ls))(*args))
    fin = np.isfinite(got)
    check(np.array_equal(fin, np.isfinite(want)),
          f"kernel vs oracle +inf pattern differs at "
          f"{int((fin != np.isfinite(want)).sum())} entries")
    check(fin.any(), "kernel admitted nothing: the check would be vacuous")
    rel_ref = np.max(np.abs(got[fin] - want[fin]) / want[fin])
    li, bi, pi = np.nonzero(fin)
    pick = rng.choice(len(li), min(len(li), 50_000), replace=False)
    li, bi, pi = li[pick], bi[pick], pi[pick]
    diff = (np.asarray(q, np.float64)[bi]
            - np.asarray(pts[li, pi], np.float64))
    exact = np.sqrt((diff ** 2).sum(-1))
    rel_64 = np.max(np.abs(got[li, bi, pi] - exact) / exact)
    log(f"kernel vs oracle: L={L} B={B} nl={nl} (ragged leaf block) "
        f"leaf_size={ls} d={d}: same +inf pattern, {fin.mean():.4f} of "
        f"entries admitted; max rel err vs oracle {rel_ref:.3e}, vs float64 "
        f"{rel_64:.3e}")
    check(rel_ref <= 1e-5, f"kernel vs oracle rel err {rel_ref:.3e} > 1e-5")
    check(rel_64 <= 1e-5, f"kernel vs float64 rel err {rel_64:.3e} > 1e-5")


def phase_build(key, device, *, n=N, d=D):
    import jax
    import repro
    from repro.api import IndexSpec

    data = make_data(jax.random.fold_in(key, 0), n, d, N_CLUSTERS)
    jax.block_until_ready(data)
    spec = IndexSpec(kind="static", **SPEC)
    t0 = time.perf_counter()
    index = repro.api.build(data, jax.random.fold_in(key, 1), spec)
    jax.block_until_ready(index.forest.point_ids)
    t_build = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(index.fused_plan().points_sorted)
    t_plan = time.perf_counter() - t0
    check(index.n_points == n, f"index holds {index.n_points} != {n} points")
    log(f"static build: n={n} d={d} K={spec.K} L={spec.L} "
        f"leaf_size={spec.leaf_size}, {index.forest.n_leaves} leaves/tree; "
        f"build {t_build:.2f}s + fused plan {t_plan:.2f}s (cold, compile "
        f"included); peak_bytes_in_use {peak_gb(device)}")
    return data, index


def phase_serve(key, index, data, *, n_queries=N_QUERIES, batch=BATCH):
    import jax
    import jax.numpy as jnp
    from repro.api import SearchRequest
    from repro.serving import Answer, ServingRuntime

    queries = np.asarray(near(key, data, n_queries))
    rt = ServingRuntime(index, k=K_NN, max_batch=batch, pad_to=batch,
                        request=SearchRequest(k=K_NN, engine="fused"))
    t0 = time.perf_counter()
    rids = [rt.submit(q) for q in queries]
    rt.flush()
    t_serve = time.perf_counter() - t0
    outs = [rt.outcomes[r] for r in rids]
    s = rt.stats
    check(all(isinstance(o, Answer) for o in outs),
          f"not every request answered: {s.summary()}")
    engines = sorted({o.engine for o in outs})
    # jaxlint: disable=engine-bypass -- asserts which engine served
    check(engines == ["fused"], f"served by engines {engines}, not fused")
    check(s.retries == 0, f"{s.retries} engine retries (fused call failed)")
    check(s.shed_total == 0, f"shed {s.shed}")
    check(s.batches == -(-n_queries // batch), f"{s.batches} batches")

    ids = np.stack([o.ids for o in outs])
    dists = np.stack([o.dists for o in outs])
    gt_ids, gt_d = exact_topk(data, jnp.asarray(queries), K_NN)
    recall = np.mean([len(set(ids[i]) & set(gt_ids[i])) / K_NN
                      for i in range(n_queries)])
    params = index.params
    ok = np.all(dists <= params.c ** 2 * gt_d + 1e-4, axis=1)
    log(f"served {s.queries} queries in {s.batches} batches of {batch} on "
        f"engines {engines}: retries={s.retries} shed={s.shed_total}; "
        f"{t_serve:.2f}s wall incl. first-batch compile")
    log(f"recall@{K_NN} vs exact = {recall:.4f} (floor {RECALL_FLOOR}); "
        f"c^2 guarantee held on {ok.mean():.4f} of queries (bound "
        f"{params.success_probability:.4f})")
    check(recall >= RECALL_FLOOR, f"recall@{K_NN} {recall:.4f} < "
          f"{RECALL_FLOOR}")
    check(ok.mean() >= params.success_probability,
          f"c^2 guarantee rate {ok.mean():.4f} < "
          f"{params.success_probability:.4f}")


def phase_stream(key, data, device, *, delta_capacity=512, n_fresh=600,
                 n_del=16):
    import jax
    import jax.numpy as jnp
    import repro
    from repro.api import IndexSpec, SearchRequest

    n = data.shape[0]
    spec = IndexSpec(kind="streaming", delta_capacity=delta_capacity, **SPEC)
    t0 = time.perf_counter()
    idx = repro.api.build(data, jax.random.fold_in(key, 1), spec)
    t_build = time.perf_counter() - t0
    segs0 = len(idx.manifest.segments)
    fresh = np.asarray(near(jax.random.fold_in(key, 2), data, n_fresh, 0.5))
    gids = idx.upsert(fresh)
    segs = len(idx.manifest.segments)
    check(segs == segs0 + 1, f"upserting {n_fresh} rows past "
          f"delta_capacity={delta_capacity} made {segs - segs0} seals")
    rng = np.random.default_rng(int(jax.random.randint(
        jax.random.fold_in(key, 3), (), 0, 2 ** 30)))
    dead = np.concatenate([
        rng.choice(n, n_del, replace=False),                   # base rows
        gids[rng.choice(delta_capacity, n_del // 2, replace=False)],  # sealed
        gids[delta_capacity + rng.choice(n_fresh - delta_capacity,
                                         n_del // 2, replace=False)]])  # delta
    check(idx.delete(dead) == len(dead), "delete missed live ids")

    alive = np.nonzero(~np.isin(gids, dead))[0]
    probe = np.concatenate([alive[:16], alive[-8:]])          # sealed + delta
    dead_vecs = np.asarray(data[jnp.asarray(dead[:n_del])])
    fresh_dead = fresh[np.searchsorted(gids, dead[n_del:])]
    qs = np.concatenate([fresh[probe], dead_vecs, fresh_dead])
    n_real = len(qs)
    qs = np.concatenate([qs, np.zeros((BATCH - n_real, qs.shape[1]),
                                      np.float32)])
    res = idx.search(jnp.asarray(qs), SearchRequest(k=K_NN, engine="fused",
                                                    n_active=n_real))
    ids = np.asarray(res.ids)[:n_real]
    dists = np.asarray(res.dists)[:n_real]
    own = ids[:len(probe), 0] == gids[probe]
    check(own.all(), f"fresh upserts not their own nearest neighbour: "
          f"{int((~own).sum())} of {len(probe)}")
    check(np.all(dists[:len(probe), 0] <= 1e-2),
          f"fresh self-distance up to {dists[:len(probe), 0].max():.3e}")
    leaked = np.isin(ids, dead)
    check(not leaked.any(), f"deleted ids returned: {ids[leaked][:8]}")
    log(f"streaming: base n={n} built in {t_build:.2f}s (build programs "
        f"compiled by the static phase); upserted "
        f"{n_fresh} > delta_capacity={delta_capacity} -> {segs} segments "
        f"(sealed with project_encode_pack); deleted {len(dead)} ids; "
        f"{len(probe)} fresh rows found as their own nearest neighbour, no "
        f"deleted id among {ids.size} results (engine={res.stats.engine}); "
        f"peak_bytes_in_use {peak_gb(device)}")


def phase_pdet(key, devices, *, n=N, d=D, n_queries=N_QUERIES,
               batch=BATCH) -> None:
    """Sharded PDET build on 4 devices vs the single-device fused engine."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    import repro
    from repro.api import IndexSpec, PlacementSpec, SearchRequest

    data = make_data(jax.random.fold_in(key, 0), n, d, N_CLUSTERS)
    queries = near(jax.random.fold_in(key, 4), data, n_queries)
    base = IndexSpec(kind="static", **SPEC)
    spec = dataclasses.replace(base, placement=PlacementSpec(
        mesh_shape=(len(devices),), mesh_axes=("data",)))
    t0 = time.perf_counter()
    pdet = repro.api.build(data, jax.random.fold_in(key, 1), spec)
    jax.block_until_ready(pdet.plan.points_sorted)
    t_build = time.perf_counter() - t0
    pts = pdet.plan.points_sorted
    shards = pts.addressable_shards
    where = sorted(s.device.id for s in shards)
    rows = sorted({s.data.shape[1] for s in shards})
    check(where == sorted(dv.id for dv in devices),
          f"points_sorted shards on devices {where}")
    check(rows == [pts.shape[1] // len(devices)],
          f"shard rows {rows} of {pts.shape[1]}")
    log(f"PDET build on {len(devices)} devices: {t_build:.2f}s (cold, "
        f"compile included); points_sorted {pts.shape} split as "
        f"{len(shards)} shards of {rows[0]} rows on devices {where}; peak "
        f"per device " + ", ".join(peak_gb(dv) for dv in devices))

    def answers(index, request):
        outs = [index.search(queries[i:i + batch], request)
                for i in range(0, n_queries, batch)]
        return (outs[0].stats, np.concatenate([np.asarray(o.ids)
                                               for o in outs]),
                np.concatenate([np.asarray(o.dists) for o in outs]))

    st, ids_p, d_p = answers(pdet, SearchRequest(k=K_NN))
    # jaxlint: disable=engine-bypass -- asserts which engine served
    check(st.engine == "pdet", f"sharded index served by {st.engine}")
    r_min = st.r_min
    del pdet, pts, shards
    gc.collect()
    det = repro.api.build(data, jax.random.fold_in(key, 1), base)
    st1, ids_f, d_f = answers(det, SearchRequest(k=K_NN, r_min=r_min,
                                                 engine="fused"))
    # jaxlint: disable=engine-bypass -- asserts which engine served
    check(st1.engine == "fused", f"single-device index served by "
          f"{st1.engine}")
    same_ids = np.array_equal(ids_p, ids_f)
    same_bits = np.array_equal(d_p.view(np.uint32), d_f.view(np.uint32))
    log(f"pdet vs fused over {n_queries} queries (r_min={r_min:.4f}): ids "
        f"identical={same_ids}, distance bits identical={same_bits}; peak "
        f"per device " + ", ".join(peak_gb(dv) for dv in devices))
    check(same_ids and same_bits, "pdet answers differ from fused")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded PDET phase on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        sys.exit(f"chip_smoke: the repro package is missing ({SRC}/repro); "
                 f"run from a checkout of the repository")
    sys.path.insert(0, str(SRC))
    import jax
    from repro.launch import compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found platform "
                 f"{dev.platform!r} ({dev.device_kind}, {len(devices)} "
                 f"device(s))")
    cache_dir = compile_cache.enable()
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")
    key = jax.random.key(args.seed)
    t0 = time.perf_counter()

    if args.four_chips:
        check(len(devices) >= 4, f"--four-chips needs 4 devices, found "
              f"{len(devices)}")
        phase_pdet(key, devices[:4])
    else:
        phase_kernel(args.seed)
        data, index = phase_build(key, dev)
        phase_serve(jax.random.fold_in(key, 4), index, data)
        del index
        gc.collect()
        phase_stream(key, data, dev)

    log(f"all phases passed in {time.perf_counter() - t0:.1f}s; compile "
        f"cache hits this run: {compile_cache.hits()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
