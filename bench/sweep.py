"""Find the knee of an open-loop serving cell: the highest offered rate at
which the serving runtime keeps up over the window.

    python3 bench/sweep.py --workload sift1m.serve-poisson --seed 5 \
        --seconds 10 --rates 40 60 80 100 120

One process sets the cell up once (data, index, warm-up, exactly as a run
does), then offers each rate in turn for ``--seconds`` through the cell's
generator and prints one JSON line per rate: the rate served, the latency
percentiles, the deepest queue, and the lag (how long after the last due
time the last answer came).  A rate the runtime keeps up with ends within
about one batch's time of the window; above the knee the queue, and with
it the lag, grows for the whole window.  The cell's rate is written into
its mix file by hand, at about four fifths of the knee.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if sys.path and os.path.abspath(sys.path[0]) == os.path.join(ROOT, "bench"):
    sys.path[0] = ROOT


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench import run as brun
    cell, cfg, mix, _, _ = brun.open_cell(args.workload)
    brun.start_jax(args.workload, cell["chips"])

    from bench import data as bdata
    from bench import traffic
    base, queries = bdata.make(cfg, args.seed)
    run = brun.Run(cfg, args.seed, base, queries)
    work = traffic.make(mix["kind"], run, mix)
    work.setup()
    for rate in args.rates:
        work.mix = dict(mix, rate_qps=rate)
        work.rt = work.runtime()
        out = work.window(args.seconds)
        print(json.dumps({
            "rate_qps": rate, "served_qps": out["served_qps"],
            "p50_ms": out["p50_ms"], "p99_ms": out["p99_ms"],
            "lag_s": out["elapsed_s"] - args.seconds,
            "max_queue": work.rt.stats.max_queue_depth,
            "batches": work.rt.stats.batches,
            "occupancy": work.counters["queries"] / max(
                1, work.counters["queries"] + work.counters["pad_queries"]),
            "failed": out["failed"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
