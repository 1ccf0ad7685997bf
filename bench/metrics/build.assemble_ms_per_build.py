"""Device ms per build in the ops of the ``assemble`` scope
(``core/detree.py:assemble_sorted_forest``: the per-tree sorted layouts
gathered and the leaf bounds reduced), from the traced window."""

from bench.program_trace import for_window


def read(ctx):
    builds = ctx.counters.get("builds")
    pt = for_window(ctx.trace)
    seconds = pt.scope_seconds("assemble") if pt is not None else None
    if not builds or seconds is None:
        return None
    return seconds / builds * 1e3
