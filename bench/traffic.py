"""The one traffic generator: it loads a mix file's ``kind`` by name.

A mix file ``mixes/<traffic>.json`` holds data: a ``kind`` and its
parameters.  The kind is the module ``kinds/<kind>.py``, found by name
like a per-layer metric's reader, whose ``Traffic(run, mix)`` has

  setup    everything before the window, compiles included;
  window   the measured loop, returning the window's numbers;
  release  drops the program's state that the check does not read;
  check    what the timed path produced, against the reference, once the
           window has closed;

and a ``counters`` dict of the program counters the readers take.  A new
arrival law is a new ``kinds/<kind>.py`` and a mix file; nothing here
changes.  The shared pieces below (building the index, logging answers,
checking them) serve every kind.  Host spans named ``bench.*`` mark every
call into the program for the trace's idle-gap attribution.
"""

from __future__ import annotations

import dataclasses
import importlib
import re

import numpy as np

from bench import reference


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def index_spec(cfg: dict):
    from repro.api import IndexSpec
    return IndexSpec(**cfg["index"])


def build_index(data, key, cfg: dict):
    """A static index and its fused search layout, built and on the
    device."""
    import jax
    import repro
    index = repro.api.build(data, key, index_spec(cfg))
    jax.block_until_ready(index.fused_plan().points_sorted)
    return index


def forest_arrays(index) -> dict:
    """A built index's forest and search layout as the plain arrays
    ``reference.forest_numbers`` checks."""
    f = index.forest
    return dict(K=f.K, L=f.L, leaf_size=f.leaf_size, A=index.A,
                point_ids=f.point_ids, valid=f.valid,
                proj_sorted=f.proj_sorted, codes_sorted=f.codes_sorted,
                leaf_lo=f.leaf_lo, leaf_hi=f.leaf_hi,
                leaf_valid=f.leaf_valid, breakpoints=f.breakpoints,
                points_sorted=index.fused_plan().points_sorted)


@dataclasses.dataclass
class QueryLog:
    """What the timed path returned, per answered query."""

    qidx: list = dataclasses.field(default_factory=list)
    ids: list = dataclasses.field(default_factory=list)
    dists: list = dataclasses.field(default_factory=list)

    def add(self, qidx, ids, dists) -> None:
        self.qidx.append(np.asarray(qidx))
        self.ids.append(np.asarray(ids))
        self.dists.append(np.asarray(dists))

    def arrays(self):
        return (np.concatenate(self.qidx), np.concatenate(self.ids),
                np.concatenate(self.dists))


def check_answers(run, log: QueryLog) -> dict:
    """Every answer of the window against the exact reference."""
    qidx, ids, dists = log.arrays()
    queries = run.queries_host[qidx]
    uq, inv = np.unique(qidx, return_inverse=True)
    gt_ids, gt_d = reference.exact_topk(run.data, run.queries_host[uq],
                                        run.k)
    ref_d = reference.pair_distances(run.data, queries, ids)
    return reference.compare_answers(
        ids, dists, gt_ids[inv], reference.pair_distances(
            run.data, queries, gt_ids[inv]), ref_d, n=run.cfg["n"],
        c=run.cfg["index"]["c"])


def make(kind: str, run, mix: dict):
    """The traffic of one mix: ``kinds/<kind>.py``'s ``Traffic``."""
    if not re.fullmatch(r"[a-z][a-z0-9_]*", kind):
        raise ValueError(f"bad traffic kind {kind!r}")
    try:
        module = importlib.import_module(f"bench.kinds.{kind}")
    except ModuleNotFoundError as e:
        if e.name != f"bench.kinds.{kind}":
            raise
        raise ValueError(f"unknown traffic kind {kind!r}: no "
                         f"bench/kinds/{kind}.py") from None
    return module.Traffic(run, mix)
