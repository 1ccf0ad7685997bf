"""Share of its roofline that the fused ``range_rerank`` kernel reaches:
the least time of each search's reranking (``roofline.range_rerank_floor``
over the search's final candidate counts) summed over the window's
searches, over the kernel's device time in the trace."""

from bench.roofline import range_rerank_floor

RANGE_RERANK = r"^range_rerank(\.\d+)?$"


def read(ctx):
    cands = ctx.counters.get("n_candidates")
    if ctx.trace is None or not cands:
        return None
    seconds = ctx.trace.op_seconds(RANGE_RERANK)
    if seconds <= 0:
        return None
    d = ctx.counters["d"]
    floor = sum(range_rerank_floor(c, d, ctx.peak) for c in cands)
    return 100.0 * floor / seconds
