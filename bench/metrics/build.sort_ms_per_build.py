"""Device ms per build in sort ops (the breakpoint sample sort and the
variadic key sort of ``core/encoding.py`` and ``core/detree.py``), from
the traced window."""

SORT = r"^sort(\.\d+)?$"


def read(ctx):
    builds = ctx.counters.get("builds")
    if ctx.trace is None or not builds or ctx.trace.op_count(SORT) == 0:
        return None
    return ctx.trace.op_seconds(SORT) / builds * 1e3
