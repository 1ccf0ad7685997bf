"""The control and the faults that the comparison deciding ``correct`` must
refuse, each planted in the program's timed path for one whole run.

  control        the plain reference put in the program's place, one step
                 below the configuration's precision: a search answers
                 with brute force from bfloat16 matmul operands (one
                 default-precision TPU pass) instead of f32 at
                 ``HIGHEST``; a build projects with bfloat16 operands.
  half           half of every search batch left out: its lanes come back
                 empty (an invalid id, distance +inf).
  altered        every answer's first id altered where it is produced.
  unsorted       the build's key sort returns its state unchanged (every
                 tree left in input order).
  prefix_sample  the breakpoints are set from the first half of the rows,
                 not from the build key's random sample.

``planted(variant)`` patches the program for the length of a ``with``
block; ``bench/test_correct.py`` (CPU) and ``bench/control.py`` (the chip,
at the cell's own size) drive whole runs of ``run.py`` under it.
"""

from __future__ import annotations

import contextlib

import numpy as np

QUERY_VARIANTS = ("control", "half", "altered")
BUILD_VARIANTS = ("control", "unsorted", "prefix_sample")


def _search_patch(fault):
    """DETLSH.search with ``fault(self, queries, request, result)`` applied
    to what it returns."""
    from repro.core import DETLSH
    search = DETLSH.search

    def patched(self, queries, request=None):
        return fault(self, queries, request, search(self, queries, request))
    return DETLSH, "search", patched


def _active(queries, request) -> int:
    return (request.n_active if request is not None and request.n_active
            else queries.shape[0])


def _control_search(self, queries, request, res):
    import jax.numpy as jnp
    from bench import reference
    m = _active(queries, request)
    ids, dists = np.array(res.ids), np.array(res.dists)
    k = ids.shape[1]
    ids[:m], dists[:m] = reference.exact_topk(
        self.data, np.asarray(queries[:m], np.float32), k,
        operands=jnp.bfloat16)
    return res._replace(ids=ids, dists=dists)


def _half(self, queries, request, res):
    m = _active(queries, request)
    ids, dists = np.array(res.ids), np.array(res.dists)
    ids[m // 2:m] = np.iinfo(np.int32).max
    dists[m // 2:m] = np.inf
    return res._replace(ids=ids, dists=dists)


def _altered(self, queries, request, res):
    ids = np.array(res.ids)
    ids[:, 0] = ids[:, 0] + 1
    return res._replace(ids=ids)


def _control_projection():
    import jax.numpy as jnp
    from repro.core import hashing

    def project(data, A, *, impl="auto"):
        return jnp.dot(data.astype(jnp.bfloat16), A.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    return hashing, "project", project


def _unsorted():
    import jax.numpy as jnp
    from repro.core import detree
    return detree, "code_sort_orders", lambda hi, lo, K: jnp.broadcast_to(
        jnp.arange(hi.shape[-1]), hi.shape)


def _prefix_sample():
    from repro.core import encoding
    select = encoding.breakpoints_sample_sort

    def prefix(coords, *args, **kw):
        return select(coords[:max(1, coords.shape[0] // 2)], *args, **kw)
    return encoding, "breakpoints_sample_sort", prefix


def patch_of(variant: str, kind: str):
    """(object, attribute, replacement) that plants ``variant`` in the
    path a traffic ``kind`` times."""
    if kind == "build_loop":
        table = {"control": _control_projection, "unsorted": _unsorted,
                 "prefix_sample": _prefix_sample}
        if variant in table:
            return table[variant]()
    else:
        table = {"control": _control_search, "half": _half,
                 "altered": _altered}
        if variant in table:
            return _search_patch(table[variant])
    raise ValueError(f"no fault {variant!r} for traffic kind {kind!r}")


@contextlib.contextmanager
def planted(variant: str | None, kind: str):
    """Run the body with ``variant`` planted (``None``: the program as it
    is)."""
    if variant is None:
        yield
        return
    obj, attr, new = patch_of(variant, kind)
    old = getattr(obj, attr)
    setattr(obj, attr, new)
    try:
        yield
    finally:
        setattr(obj, attr, old)
