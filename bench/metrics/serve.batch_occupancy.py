"""Share of the serving runtime's batch lanes that carried a real query:
queries / (queries + pad_queries) from ``RuntimeStats``."""


def read(ctx):
    q = ctx.counters.get("queries")
    pad = ctx.counters.get("pad_queries")
    if not q or pad is None:
        return None
    return 100.0 * q / (q + pad)
