"""StreamingDETLSH: the mutable, segmented DET-LSH index.

Structure (docs/DESIGN.md §5): a ``Manifest`` of sealed ``Segment``s plus
one mutable ``Memtable`` delta.  Inserts append to the delta (answered
exactly until sealed); deletes tombstone wherever the point lives; sealing
encodes the delta with the base build's frozen breakpoints; compaction
merges sealed segments on the host and atomically swaps the result in.

Queries fan out over {segments + delta}: each sealed segment runs the
ordinary batched c^2-k-ANN (fused or vmap engine) over its own forest with
its tombstone mask, the delta is answered by exact brute force over its
<= capacity rows, and the per-source top-k lists — in *global* id space —
are combined through ``core/candidates.py`` (merge_round dedup +
canonicalize), so the cross-source merge is the same property-tested
machinery the round loop uses.

Guarantee argument (docs/DESIGN.md §5): each segment query is a standard
DET-LSH query over that segment's live points (T1 uses the segment's total
row count n_seg >= n_live, which only delays termination — a superset, safe
by the §2 argument), the delta is exact, and the final k is the best-of-
union — so recall over the surviving union is bounded below by the paper's
per-segment guarantee.

Note on jit: ``query`` is trace-compatible (pure jnp on device state) when
``r_min`` is passed explicitly; the default estimates r_min host-side.
Mutations change device buffers, so re-trace after upsert/seal/compact if
you wrapped ``query`` in ``jax.jit``.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import warnings
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import registry as engine_registry
from repro.core import estimate_r_min, hashing
from repro.core import candidates as cand
from repro.core import encoding as enc
from repro.core.query import QueryResult, knn_query_batch
from repro.core.theory import LSHParams, derive_params
from repro.streaming.compactor import merge_segments
from repro.streaming.manifest import Manifest
from repro.streaming.memtable import Memtable
from repro.streaming.segment import Segment, build_segment

_DELTA = "delta"     # locator tag for rows still in the memtable


class _SegView(NamedTuple):
    """One segment's pinned query inputs.

    Device arrays are immutable, so pinning = holding references taken at
    pin time: a later ``mark_dead`` replaces the segment's *caches* but
    never mutates the arrays an earlier pin captured.  ``live_host`` is a
    copy (the host bitmap does mutate in place) — it exists for
    ``PinnedView.survivors()``, the oracle input, not for the query path.
    """

    seg: Segment
    live_dev: Optional[jax.Array]         # (m,) bool, None = all live
    live_sorted_dev: Optional[jax.Array]  # (L, n_pad) bool, None = all live
    gmap: jax.Array                       # (m+1,) int32 local -> global id
    live_host: np.ndarray                 # (m,) bool copy at pin time


@dataclasses.dataclass(frozen=True)
class PinnedView:
    """An immutable epoch of a ``StreamingDETLSH`` (docs/DESIGN.md §9).

    Everything a query needs is captured by reference-to-immutable (device
    arrays, sealed segment rows) or by copy (host bitmaps, delta rows), so
    any interleaving of upsert/delete/seal/compact after the pin leaves
    this view answering exactly as the index did at pin time.  The view is
    what the serving runtime's epoch wraps; ``search(queries, request,
    view=...)`` runs the ordinary fan-out against it.
    """

    manifest_version: int
    memtable_version: int
    id_capacity: int                      # combine sentinel / bitmap width
    segs: tuple                           # of _SegView (n_live > 0 only)
    delta: Optional[tuple]                # (vecs, live, gmap) device arrays
    delta_n_live: int
    delta_capacity: int
    delta_host: Optional[tuple]           # (vecs, gids, live) host copies
    # per-view r_min cache (the index cache is keyed by *current* versions)
    _rmin: dict = dataclasses.field(default_factory=dict, repr=False,
                                    compare=False)

    @property
    def fingerprint(self) -> tuple:
        return (self.manifest_version, self.memtable_version)

    @property
    def n_live(self) -> int:
        return (sum(int(v.live_host.sum()) for v in self.segs)
                + self.delta_n_live)

    def survivors(self) -> tuple:
        """(vectors, gids) alive at pin time — the from-scratch-rebuild
        oracle input for the epoch equivalence property test."""
        vecs = [np.asarray(v.seg.data)[v.live_host] for v in self.segs]
        gids = [v.seg.gids[v.live_host].astype(np.int64) for v in self.segs]
        if self.delta_host is not None:
            dv, dg, dl = self.delta_host
            vecs.append(dv[dl])
            gids.append(dg[dl])
        if not vecs:
            d = (self.segs[0].seg.data.shape[1] if self.segs
                 else (self.delta_host[0].shape[1] if self.delta_host
                       else 0))
            return np.zeros((0, d), np.float32), np.zeros(0, np.int64)
        return np.concatenate(vecs), np.concatenate(gids)


class StreamingDETLSH:
    """Mutable segmented DET-LSH index with upsert / delete / compaction.

    Satisfies ``repro.api.MutableAnnIndex``: the typed ``search`` surface
    plus ``upsert``/``delete``/``maybe_compact`` and snapshot ``save``.
    """

    def __init__(self, params: LSHParams, A: jax.Array, bp_all: jax.Array,
                 base: Optional[Segment], *, Nr: int, leaf_size: int,
                 delta_capacity: int = 512, max_segments: int = 4,
                 id_capacity: int = 1 << 20,
                 build_impl: str = "auto", build_chunk: int = 512):
        self.params = params
        self.A = A
        self.bp_all = bp_all              # (L*K, Nr+1) frozen breakpoints
        self.Nr = Nr
        self.leaf_size = leaf_size
        self.build_impl = build_impl      # seal-path builder (DESIGN.md §8)
        self.build_chunk = build_chunk
        self.max_segments = max_segments
        self.id_capacity = int(id_capacity)
        self.manifest = Manifest()
        self.locator: Dict[int, Tuple] = {}   # gid -> (_DELTA, slot) | (seg_id, row)
        self.next_gid = 0
        self._next_seg_id = 0
        d = A.shape[0]
        self.memtable = Memtable(delta_capacity, d)
        self._delta_cache = None          # (memtable.version, device arrays)
        self.spec = None                  # IndexSpec when built via from_spec
        # ((manifest.version, memtable.version), {k: r_min}) — the per-k
        # radius-estimate cache, invalidated by structural mutation.
        self._rmin_cache: Tuple[Tuple[int, int], Dict[int, float]] = \
            ((-1, -1), {})
        if base is not None:
            self.manifest.add(base)
            self._next_seg_id = base.seg_id + 1
            for row, gid in enumerate(base.gids):
                self.locator[int(gid)] = (base.seg_id, row)
            self.next_gid = int(base.gids.max()) + 1 if base.m else 0

    # ------------------------------------------------------------------
    # Build
    # ------------------------------------------------------------------

    @classmethod
    def build(cls, data: jax.Array, key: jax.Array,
              params: LSHParams | None = None, *,
              Nr: int = enc.DEFAULT_NR, leaf_size: int = 64,
              delta_capacity: int = 512, max_segments: int = 4,
              id_capacity: int | None = None,
              breakpoint_method: str = "sample_sort",
              project_impl: str = "auto",
              encode_impl: str = "auto",
              build_impl: str = "auto",
              build_chunk: int = 512) -> "StreamingDETLSH":
        """Static base build (Alg. 1 + 2) that also freezes the breakpoints
        every later seal will encode with.  ``build_impl``/``build_chunk``
        select the fused single-sort builder for the base build and every
        later seal (docs/DESIGN.md §8)."""
        params = params or derive_params()
        data = jnp.asarray(data, jnp.float32)
        n, d = data.shape
        kp, kb = jax.random.split(key)
        A = hashing.sample_projections(kp, d, params.K, params.L)
        proj = hashing.project(data, A, impl=project_impl)
        bp_all = enc.select_breakpoints(proj, Nr, method=breakpoint_method,
                                        key=kb)
        base = build_segment(data, np.arange(n, dtype=np.int64), A, params,
                             bp_all, Nr=Nr, leaf_size=leaf_size, seg_id=0,
                             proj=proj, encode_impl=encode_impl,
                             build_impl=build_impl, build_chunk=build_chunk)
        if id_capacity is None:
            id_capacity = max(2 * n, n + 16 * delta_capacity, 1024)
        return cls(params, A, bp_all, base, Nr=Nr, leaf_size=leaf_size,
                   delta_capacity=delta_capacity, max_segments=max_segments,
                   id_capacity=id_capacity, build_impl=build_impl,
                   build_chunk=build_chunk)

    @classmethod
    def from_spec(cls, data: jax.Array, key: jax.Array,
                  spec) -> "StreamingDETLSH":
        """Build from one declarative ``repro.api.IndexSpec``."""
        if spec.kind != "streaming":
            raise ValueError(f"StreamingDETLSH.from_spec needs "
                             f"kind='streaming', got {spec.kind!r} "
                             f"(use repro.api.build)")
        idx = cls.build(data, key, spec.derive_params(), Nr=spec.Nr,
                        leaf_size=spec.leaf_size,
                        delta_capacity=spec.delta_capacity,
                        max_segments=spec.max_segments,
                        id_capacity=spec.id_capacity,
                        breakpoint_method=spec.breakpoint_method,
                        project_impl=spec.project_impl,
                        encode_impl=spec.encode_impl,
                        build_impl=spec.build_impl,
                        build_chunk=spec.build_chunk)
        idx.spec = spec
        return idx

    # ------------------------------------------------------------------
    # Mutation
    # ------------------------------------------------------------------

    def upsert(self, vectors, gids=None) -> np.ndarray:
        """Insert (or overwrite) rows; returns their global ids (int32).

        Overwrite semantics: an existing gid is tombstoned wherever it
        lives and re-inserted into the delta.  Sealing triggers itself when
        the delta fills; compaction is the caller's trigger
        (``maybe_compact``, wired into serving.LSHService).
        """
        vecs = np.asarray(vectors, np.float32)
        if vecs.ndim == 1:
            vecs = vecs[None, :]
        m = len(vecs)
        if gids is None:
            gids = np.arange(self.next_gid, self.next_gid + m, dtype=np.int64)
        else:
            gids = np.asarray(gids, np.int64).reshape(-1)
            assert len(gids) == m, (len(gids), m)
        if m == 0:
            return gids.astype(np.int32)
        # Validate before mutating any state so the caller can recover.
        self.next_gid = self.check_upsert(gids)

        # Last write wins within one call: keep only each gid's final row.
        _, last_rev = np.unique(gids[::-1], return_index=True)
        keep = np.sort(m - 1 - last_rev, kind="stable")
        ins_gids, ins_vecs = gids[keep], vecs[keep]
        for gid in ins_gids:                       # overwrite semantics
            if int(gid) in self.locator:
                self._tombstone(int(gid))
        # Bulk-copy into the delta in capacity-sized blocks (the per-row
        # Python loop made ingest interpreter-bound), sealing at each fill.
        pos = 0
        while pos < len(ins_gids):
            if self.memtable.full:
                self.seal()
            take = min(self.memtable.capacity - self.memtable.count,
                       len(ins_gids) - pos)
            slots = self.memtable.add_block(ins_gids[pos:pos + take],
                                            ins_vecs[pos:pos + take])
            self.locator.update(
                (int(g), (_DELTA, int(s)))
                for g, s in zip(ins_gids[pos:pos + take], slots))
            pos += take
        if self.memtable.full:
            self.seal()
        return gids.astype(np.int32)

    def check_upsert(self, gids) -> int:
        """Validate an upsert's global ids *without mutating anything*;
        returns the post-insert ``next_gid``.  Shared by ``upsert`` and by
        write-ahead wrappers (durability/durable.py) that must know an op
        will be accepted before logging it."""
        gids = np.asarray(gids, np.int64).reshape(-1)
        if len(gids) == 0:
            return self.next_gid
        if gids.min() < 0:
            raise ValueError(f"gids must be non-negative, got {gids.min()}")
        new_next = max(self.next_gid, int(gids.max()) + 1)
        if new_next > self.id_capacity:
            raise ValueError(
                f"gid space exhausted ({new_next} > id_capacity="
                f"{self.id_capacity}); call grow_id_capacity() (one-time "
                f"recompile of the combine step) or build a larger index")
        return new_next

    def delete(self, gids) -> int:
        """Tombstone points by global id; returns how many existed."""
        return sum(self._tombstone(int(g)) for g in np.atleast_1d(gids))

    def _tombstone(self, gid: int) -> bool:
        loc = self.locator.pop(gid, None)
        if loc is None:
            return False
        where, pos = loc
        if where == _DELTA:
            self.memtable.kill(pos)
        else:
            self._segment(where).mark_dead(pos)
        return True

    def _segment(self, seg_id: int) -> Segment:
        for s in self.manifest.segments:
            if s.seg_id == seg_id:
                return s
        raise KeyError(seg_id)

    def seal(self) -> Optional[Segment]:
        """Freeze the delta into a sealed segment (frozen-breakpoint encode).

        All ``capacity`` slots seal — already-dead slots become tombstoned
        rows (compaction drops them) — so every sealed-from-delta segment
        has identical shapes and reuses the same compiled query kernels.
        """
        mt = self.memtable
        if mt.count == 0:
            return None
        seg = build_segment(mt.vecs, mt.gids, self.A, self.params,
                            self.bp_all, Nr=self.Nr,
                            leaf_size=self.leaf_size,
                            seg_id=self._next_seg_id, live=mt.live,
                            build_impl=self.build_impl,
                            build_chunk=self.build_chunk)
        self._next_seg_id += 1
        self.manifest.add(seg)
        for slot in range(mt.count):
            if mt.live[slot]:
                self.locator[int(mt.gids[slot])] = (seg.seg_id, slot)
        mt.reset()
        return seg

    flush = seal

    def compact(self) -> bool:
        """Merge all sealed segments into one, dropping tombstones (O(n)
        sorted-array merge on the host; see streaming/compactor.py)."""
        segs = self.manifest.segments
        if len(segs) <= 1 and not any(s.has_tombstones for s in segs):
            return False
        merged = merge_segments(segs, leaf_size=self.leaf_size,
                                seg_id=self._next_seg_id)
        self._next_seg_id += 1
        self.manifest.swap([s.seg_id for s in segs],
                           [merged] if merged is not None else [])
        if merged is not None:
            for row, gid in enumerate(merged.gids):
                self.locator[int(gid)] = (merged.seg_id, row)
        return True

    def grow_id_capacity(self, new_capacity: int) -> None:
        """Enlarge the global id space (the combine step's bitmap width and
        invalid-id sentinel).  Existing gids are untouched; the next query
        recompiles once for the new shapes."""
        if new_capacity < self.id_capacity:
            raise ValueError(f"cannot shrink id_capacity "
                             f"({new_capacity} < {self.id_capacity})")
        self.id_capacity = int(new_capacity)
        self._delta_cache = None          # gmap sentinel baked the old value

    def maybe_compact(self) -> bool:
        """The service's compaction trigger: compact when the fan-out width
        exceeds ``max_segments`` (in production this runs on a background
        thread; the swap itself is atomic either way)."""
        if len(self.manifest.segments) > self.max_segments:
            return self.compact()
        return False

    def requantile(self, key: jax.Array | None = None) -> None:
        """Full rebuild with fresh breakpoints over the surviving points —
        the escape hatch when ``clip_fraction()`` says the frozen
        quantization has drifted too far (docs/DESIGN.md §5)."""
        vecs, gids = self._survivors()
        if len(gids) == 0:
            raise ValueError("cannot requantile an empty index")
        data = jnp.asarray(vecs)
        proj = hashing.project(data, self.A)
        self.bp_all = enc.select_breakpoints(
            proj, self.Nr, key=key)
        base = build_segment(data, gids, self.A, self.params, self.bp_all,
                             Nr=self.Nr, leaf_size=self.leaf_size,
                             seg_id=self._next_seg_id, proj=proj,
                             build_impl=self.build_impl,
                             build_chunk=self.build_chunk)
        self._next_seg_id += 1
        self.manifest = Manifest()
        self.manifest.add(base)
        self.memtable.reset()
        self._delta_cache = None
        self.locator = {int(g): (base.seg_id, row)
                        for row, g in enumerate(base.gids)}

    def _survivors(self) -> tuple[np.ndarray, np.ndarray]:
        vecs = [np.asarray(s.data)[s.live] for s in self.manifest.segments]
        gids = [s.gids[s.live].astype(np.int64)
                for s in self.manifest.segments]
        mt = self.memtable
        if mt.n_live:
            vecs.append(mt.vecs[mt.live])
            gids.append(mt.gids[mt.live])
        if not vecs:
            return (np.zeros((0, self.A.shape[0]), np.float32),
                    np.zeros(0, np.int64))
        return np.concatenate(vecs), np.concatenate(gids)

    # ------------------------------------------------------------------
    # Query
    # ------------------------------------------------------------------

    def _delta_device(self):
        mt = self.memtable
        if self._delta_cache is None or self._delta_cache[0] != mt.version:
            gmap = np.where(mt.live, mt.gids,
                            self.id_capacity).astype(np.int32)
            # jnp.array copies: the memtable buffers mutate in place and the
            # CPU backend may otherwise alias them zero-copy.
            self._delta_cache = (mt.version,
                                 (jnp.array(mt.vecs), jnp.array(mt.live),
                                  jnp.asarray(gmap)))
        return self._delta_cache[1]

    # ------------------------------------------------------------------
    # Epoch views (docs/DESIGN.md §9)
    # ------------------------------------------------------------------

    def _current_view(self) -> PinnedView:
        """The view of the *current* structure — the ordinary query path
        (one code path: a plain ``search`` is a search on a just-pinned
        view, so epoch answers can never drift from live answers)."""
        mt = self.memtable
        return PinnedView(
            manifest_version=self.manifest.version,
            memtable_version=mt.version,
            id_capacity=self.id_capacity,
            segs=tuple(
                _SegView(seg, seg.live_dev(), seg.live_sorted_dev(),
                         seg.gid_map_dev(self.id_capacity), seg.live)
                for seg in self.manifest.segments if seg.n_live > 0),
            delta=self._delta_device() if mt.n_live > 0 else None,
            delta_n_live=mt.n_live, delta_capacity=mt.capacity,
            delta_host=None)

    def pin_state(self) -> PinnedView:
        """Pin the current epoch: an immutable view that keeps answering
        exactly as of now, across any later upsert/delete/seal/compact.

        Device arrays are pinned by reference (they never mutate — later
        deletes replace segment *caches*, old arrays survive through the
        view); host bitmaps and delta rows are pinned by copy, so the
        view's ``survivors()`` oracle stays frozen too."""
        cur = self._current_view()
        mt = self.memtable
        return dataclasses.replace(
            cur,
            segs=tuple(v._replace(live_host=v.live_host.copy())
                       for v in cur.segs),
            delta_host=((mt.vecs.copy(), mt.gids.copy(), mt.live.copy())
                        if mt.count > 0 else None))

    def _query_delta(self, view: PinnedView, queries: jax.Array, k: int,
                     n_active: Optional[jax.Array | int] = None):
        """Exact top-k over the delta rows (bounded, one stable shape).

        Direct (q - v)^2 differences, not the qq - 2qc + pp expansion: the
        delta is small enough that the O(B*cap*d) intermediate is cheap, and
        the direct form avoids the expansion's cancellation error (the delta
        is the 'exact' tier of the index — keep it exact).  Pad lanes
        (>= n_active) admit nothing, matching the segment engines."""
        vecs, live, gmap = view.delta
        diff = queries[:, None, :] - vecs[None, :, :]
        dist = jnp.sqrt(jnp.sum(diff * diff, axis=-1))
        dist = jnp.where(live[None, :], dist, jnp.inf)
        if n_active is not None:
            lane_ok = jnp.arange(queries.shape[0]) < jnp.asarray(n_active)
            dist = jnp.where(lane_ok[:, None], dist, jnp.inf)
        kk = min(k, view.delta_capacity)
        negd, sel = jax.lax.top_k(-dist, kk)
        # +inf slots (dead rows, masked pad lanes) must not leak their gid.
        ids = jnp.where(jnp.isfinite(negd), gmap[sel], view.id_capacity)
        return ids, -negd

    def _combine(self, sources: List[Tuple[jax.Array, jax.Array]],
                 k: int, B: int, nid: int):
        """Fold per-source (global ids, exact dists) top-k lists into the
        overall top-k via the incremental candidate merge.  ``nid`` is the
        view's pinned invalid-id sentinel / bitmap width."""
        cap = sum(int(ids.shape[1]) for ids, _ in sources)
        state = cand.CandidateState(
            ids=jnp.full((B, cap), nid, jnp.int32),
            dists=jnp.full((B, cap), jnp.inf, jnp.float32),
            seen=jnp.zeros((B, cand.bitmap_words(nid)), jnp.uint32),
            count=jnp.zeros((B,), jnp.int32))
        mr = jax.vmap(functools.partial(cand.merge_round, nid))
        for ids_s, d_s in sources:
            state = mr(state, ids_s.astype(jnp.int32), d_s)
        ids_c, d_c = jax.vmap(functools.partial(cand.canonicalize, nid))(
            state.ids, state.dists)
        if cap < k:
            ids_c = jnp.pad(ids_c, ((0, 0), (0, k - cap)),
                            constant_values=nid)
            d_c = jnp.pad(d_c, ((0, 0), (0, k - cap)),
                          constant_values=jnp.inf)
        return ids_c[:, :k], d_c[:, :k]

    def _rmin_entries(self) -> Dict[int, float]:
        """The per-k radius cache for the *current* structure version —
        the single place the (manifest, memtable) cache key lives.
        Resets the cache when the tag is stale."""
        tag = (self.manifest.version, self.memtable.version)
        if self._rmin_cache[0] != tag:
            self._rmin_cache = (tag, {})
        return self._rmin_cache[1]

    def _rmin_hit(self, k: int) -> bool:
        """Whether ``r_min_for(k)`` would be a cache hit right now."""
        return k in self._rmin_entries()

    def r_min_for(self, k: int, queries: jax.Array | None = None) -> float:
        """Cached per-(index, k) starting radius over the current structure.

        Estimated once per (index state, k) — on the first ``r_min=None``
        search, from that batch's queries (segment rows stand in as probes
        when no queries are given) — and keyed by (manifest, memtable)
        versions so structural mutations invalidate it.  Segment-internal
        tombstones don't bump a version — a slightly stale estimate only
        shifts the starting radius, never correctness (the guarantee holds
        for any r_min)."""
        cache = self._rmin_entries()
        if k not in cache:
            segs = [s for s in self.manifest.segments if s.n_live > 0]
            ref = (segs[0].data if segs else jnp.asarray(self.memtable.vecs))
            probes = (queries if queries is not None
                      else ref[: min(64, ref.shape[0])])
            cache[k] = estimate_r_min(ref, probes, k, self.params.c)
        return cache[k]

    def _fanout_query(self, queries: jax.Array, req, r_min: float,
                      view: PinnedView) -> QueryResult:
        """Batched c^2-k-ANN over a view's live point set (fan-out +
        combine).  Returned ids are *global* ids; invalid slots carry the
        view's ``id_capacity`` and +inf."""
        queries = jnp.asarray(queries, jnp.float32)
        B = queries.shape[0]
        k, n_active = req.k, req.n_active

        spec = self.spec
        block_q = spec.block_q if spec is not None else 8
        block_l = spec.block_l if spec is not None else 8
        probe_default = spec.probe_depth if spec is not None else 0
        sources, rounds, n_cands, final_r = [], [], [], []
        probed, pcand = [], []
        for sv in view.segs:
            seg = sv.seg
            cfg = req.to_query_config(k=min(k, seg.m), r_min=r_min,
                                      block_q=block_q, block_l=block_l,
                                      default_probe_depth=probe_default)
            fused = engine_registry.resolve_engine(
                cfg.engine, mode=cfg.mode, batch=B) == "fused"
            res = knn_query_batch(
                seg.data, seg.forest, self.A, self.params, queries, cfg,
                plan=seg.plan() if fused else None, live=sv.live_dev,
                live_sorted=sv.live_sorted_dev, n_active=n_active)
            sources.append((sv.gmap[res.ids], res.dists))
            rounds.append(res.rounds)
            n_cands.append(res.n_candidates)
            final_r.append(res.final_r)
            if res.probed_leaves is not None:
                probed.append(res.probed_leaves)
                pcand.append(res.probe_candidates)
        if view.delta is not None:
            ids_d, d_d = self._query_delta(view, queries, k, n_active)
            sources.append((ids_d, d_d))
            delta_cand = jnp.full((B,), view.delta_n_live, jnp.int32)
            if n_active is not None:
                delta_cand = jnp.where(jnp.arange(B) < jnp.asarray(n_active),
                                       delta_cand, 0)
            n_cands.append(delta_cand)

        if not sources:
            return QueryResult(
                ids=jnp.full((B, k), view.id_capacity, jnp.int32),
                dists=jnp.full((B, k), jnp.inf, jnp.float32),
                rounds=jnp.zeros((B,), jnp.int32),
                n_candidates=jnp.zeros((B,), jnp.int32),
                final_r=jnp.full((B,), r_min, jnp.float32),
                probed_leaves=jnp.zeros((B,), jnp.int32),
                probe_candidates=jnp.zeros((B,), jnp.int32))

        ids, dists = self._combine(sources, k, B, view.id_capacity)
        zero = jnp.zeros((B,), jnp.int32)
        return QueryResult(
            ids=ids, dists=dists,
            rounds=functools.reduce(jnp.maximum, rounds, zero),
            n_candidates=functools.reduce(jnp.add, n_cands, zero),
            final_r=functools.reduce(
                jnp.maximum, final_r, jnp.full((B,), r_min, jnp.float32)),
            probed_leaves=functools.reduce(jnp.add, probed, zero),
            probe_candidates=functools.reduce(jnp.add, pcand, zero))

    def _view_rmin(self, view: PinnedView, k: int,
                   probes: jax.Array) -> float:
        """Per-(view, k) starting-radius estimate — cached *on the view*
        (the index cache is keyed by current versions, which a pinned
        epoch must not consult after a mutation)."""
        if k not in view._rmin:
            if view.segs:
                ref = view.segs[0].seg.data
            elif view.delta is not None:
                ref = view.delta[0]
            else:
                view._rmin[k] = 1.0                    # empty view
                return 1.0
            probes = probes if probes is not None and len(probes) \
                else ref[: min(64, ref.shape[0])]
            view._rmin[k] = estimate_r_min(ref, probes, k, self.params.c)
        return view._rmin[k]

    def search(self, queries: jax.Array, request=None, *,
               view: Optional[PinnedView] = None):
        """Typed batched search over the live point set
        (``repro.api.SearchRequest`` in, ``repro.api.SearchResult`` out).
        Trace-compatible when the request carries an explicit ``r_min``.

        ``view`` pins the search to an epoch from ``pin_state()``: the
        answer is computed over the view's frozen structure regardless of
        any mutation since the pin (the serving runtime's RCU read path).
        """
        from repro.api.request import SearchRequest, SearchResult, \
            SearchStats
        req = request or SearchRequest()
        if req.engine is None and self.spec is not None:
            req = dataclasses.replace(req, engine=self.spec.engine)
        r_min, cached = req.r_min, False
        current = (view is None
                   or view.fingerprint == (self.manifest.version,
                                           self.memtable.version))
        if r_min is None:
            cached = (self._rmin_hit(req.k) if current    # hit vs first
                      else req.k in view._rmin)           # estimate
            probes = None
            if not cached:
                # Zero-vector pad lanes must not skew the estimate (n_active
                # == 0 keeps the full batch: no real lanes to probe).
                probes = queries[: req.n_active] if req.n_active else queries
            if current:
                r_min = self.r_min_for(req.k, probes)
                if view is not None:
                    view._rmin.setdefault(req.k, r_min)
            else:
                r_min = self._view_rmin(view, req.k, probes)
        res = self._fanout_query(queries, req, float(r_min),
                                 view if view is not None
                                 else self._current_view())
        engine = engine_registry.resolve_engine(
            req.engine, mode=req.mode, batch=jnp.asarray(queries).shape[0])
        return SearchResult(
            ids=res.ids, dists=res.dists,
            stats=SearchStats(engine=engine, r_min=float(r_min),
                              r_min_cached=cached, rounds=res.rounds,
                              n_candidates=res.n_candidates,
                              final_r=res.final_r,
                              probed_leaves=res.probed_leaves,
                              probe_candidates=res.probe_candidates),
            raw=res)

    def query(self, queries: jax.Array, k: int = 10, *,
              r_min: float | None = None, M: int = 8, mode: str = "leaf",
              max_rounds: int = 48, engine: str = "auto",
              n_active: int | None = None) -> QueryResult:
        """Deprecated kwarg surface — use ``search(queries,
        repro.api.SearchRequest(...))``.  Kept as a thin shim for the
        seed-era callers; returns the engine-level ``QueryResult``."""
        warnings.warn(
            "StreamingDETLSH.query(**kwargs) is deprecated; use "
            "StreamingDETLSH.search(queries, repro.api.SearchRequest(...))",
            DeprecationWarning, stacklevel=2)
        from repro.api.request import SearchRequest
        req = SearchRequest(k=k, r_min=r_min, M=M, mode=mode,
                            max_rounds=max_rounds, engine=engine,
                            n_active=n_active)
        return self.search(queries, req).raw

    def save(self, path) -> None:
        """Write a versioned snapshot directory (``repro.api.load``):
        segments (rows, gids, tombstones, forests), memtable survivors,
        frozen breakpoints, and the manifest."""
        from repro.api import persist
        persist.save_streaming(self, path)

    def warmup_query_caches(self) -> None:
        """Eagerly materialize per-segment device caches (fused plans,
        tombstone masks, gid maps) and the delta snapshot.  Call after
        mutations and before jitting ``query`` so the trace captures
        concrete arrays rather than re-staging them as constants."""
        for seg in self.manifest.segments:
            seg.warm_caches(self.id_capacity)
        self._delta_device()

    # ------------------------------------------------------------------
    # Introspection
    # ------------------------------------------------------------------

    @property
    def n_live(self) -> int:
        return self.manifest.n_live + self.memtable.n_live

    @property
    def n_points(self) -> int:
        """AnnIndex protocol: the live point count."""
        return self.n_live

    @property
    def n_total(self) -> int:
        return self.manifest.n_rows + self.memtable.count

    def clip_fraction(self) -> float:
        """Rows-weighted breakpoint-drift signal over sealed segments
        (coords of sealed inserts outside the frozen outer edges)."""
        total = sum(s.m for s in self.manifest.segments)
        if total == 0:
            return 0.0
        return sum(s.clip_fraction * s.m
                   for s in self.manifest.segments) / total

    def index_size_bytes(self) -> int:
        return (sum(s.forest.size_bytes() for s in self.manifest.segments)
                + self.A.size * 4)

    def state_digest(self) -> str:
        """sha256 fingerprint of the complete *logical* state: every array
        and counter that determines answers or future mutations (segments
        with their tombstone bitmaps and forests, memtable buffers, id
        allocation, frozen breakpoints).  Caches and version counters are
        excluded — they are performance state.  Equal digests mean two
        indexes are bit-identical; this is the recovered ≡ pre-crash
        oracle in tests/test_durability*.py (docs/DESIGN.md §13)."""
        h = hashlib.sha256()

        def put(a, dtype=None):
            x = np.asarray(a)
            if dtype is not None:
                x = x.astype(dtype)
            h.update(np.ascontiguousarray(x).tobytes())

        for v in (self.next_gid, self._next_seg_id, self.id_capacity,
                  self.Nr, self.leaf_size, self.memtable.count):
            h.update(int(v).to_bytes(8, "little", signed=True))
        put(self.A, np.float32)
        put(self.bp_all, np.float32)
        for seg in sorted(self.manifest.segments, key=lambda s: s.seg_id):
            h.update(int(seg.seg_id).to_bytes(8, "little", signed=True))
            h.update(np.float64(seg.clip_fraction).tobytes())
            put(seg.data, np.float32)
            put(seg.gids, np.int64)
            put(seg.live, np.uint8)
            for name in ("point_ids", "proj_sorted", "codes_sorted",
                         "valid", "leaf_lo", "leaf_hi", "leaf_valid",
                         "breakpoints"):
                put(getattr(seg.forest, name))
        mt = self.memtable
        put(mt.vecs, np.float32)
        put(mt.gids, np.int64)
        put(mt.live, np.uint8)
        return h.hexdigest()

    def stats(self) -> dict:
        return {
            "n_live": self.n_live, "n_total": self.n_total,
            "delta_rows": self.memtable.count,
            "delta_live": self.memtable.n_live,
            "clip_fraction": round(self.clip_fraction(), 6),
            "manifest": self.manifest.describe(),
        }
