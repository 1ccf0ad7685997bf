"""Host ms per search batch spent tracing, lowering and compiling (or
loading from the persistent compile cache) inside the program's
``detlsh.search.dispatch`` span: (sum of its ``trace_ms``, ``lower_ms``
and ``compile_ms`` args) / the window's dispatch spans."""

from bench.program_trace import for_window

SPAN = "detlsh.search.dispatch"


def read(ctx):
    pt = for_window(ctx.trace)
    spans = pt.named(SPAN) if pt is not None else []
    if not spans:
        return None
    return sum(a["trace_ms"] + a["lower_ms"] + a["compile_ms"]
               for _, _, _, a in spans) / len(spans)
