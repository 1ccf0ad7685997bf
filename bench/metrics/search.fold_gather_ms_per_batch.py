"""Device ms per search batch in the ops of the fused round loop's
``fold`` scope (``core/query.py:fused_query_batch``: each tree's row of the
round's distances gathered into id order and min-folded), from the traced
window."""

from bench.program_trace import for_window


def read(ctx):
    batches = ctx.counters.get("batches")
    pt = for_window(ctx.trace)
    seconds = pt.scope_seconds("fold") if pt is not None else None
    if not batches or seconds is None:
        return None
    return seconds / batches * 1e3
