"""Mean ms a served query waited in the runtime's queue, from its arrival
to its batch's start: (sum of ``wait_ms_sum``) / (sum of ``queries``) over
the window's ``detlsh.serve.batch`` spans (``serving/runtime.py``)."""

from bench.program_trace import for_window

SPAN = "detlsh.serve.batch"


def read(ctx):
    pt = for_window(ctx.trace)
    spans = pt.named(SPAN) if pt is not None else []
    queries = sum(a.get("queries", 0) for _, _, _, a in spans)
    if not queries:
        return None
    return sum(a.get("wait_ms_sum", 0.0) for _, _, _, a in spans) / queries
