"""Device ms per search batch outside the ``range_rerank`` kernel: the
per-round fold into the candidate table, the round update and the final
top-k of ``core/query.py:fused_query_batch``, from the traced window."""

RANGE_RERANK = r"^range_rerank(\.\d+)?$"


def read(ctx):
    batches = ctx.counters.get("batches")
    if ctx.trace is None or not batches or not ctx.trace.ops:
        return None
    return ctx.trace.op_seconds(exclude=RANGE_RERANK) / batches * 1e3
