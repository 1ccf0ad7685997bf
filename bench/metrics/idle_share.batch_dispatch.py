"""Share of the traced window in which no operation runs on the device
while the host is inside the program's ``detlsh.search.dispatch`` span
(the call into the search engine: tracing, lowering and dispatching the
round loop), %."""

from bench.program_trace import for_window

SPAN = "detlsh.search.dispatch"


def read(ctx):
    pt = for_window(ctx.trace)
    idle = pt.idle_inside(SPAN) if pt is not None else None
    if idle is None or pt.window_s <= 0:
        return None
    return 100.0 * idle / pt.window_s
