"""Mean radius rounds per answered query (``SearchStats.rounds``), a
program counter of the fused round loop (``core/query.py``)."""

import numpy as np


def read(ctx):
    rounds = ctx.counters.get("rounds")
    if rounds is None or len(rounds) == 0:
        return None
    return float(np.mean(rounds))
