"""Readings of the control and of planted faults at a cell's own size, for
setting the limits that decide ``correct``.

    python3 bench/control.py --workload sift1m.batch64 --seeds 1 2 3 \
        --variant control --seconds 3

For each seed, one whole run of the cell (``run.py``: data,
build, warm-up, a short window at the cell's own load, the comparison)
with the variant planted in the timed path (``faults.py``).  Each reading
is one JSON line: the run's ``correct`` and every number it compared,
beside its limit.  One variant to a process: a fault planted under a jitted
build would not reach a program that an earlier run traced.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if sys.path and os.path.abspath(sys.path[0]) == os.path.join(ROOT, "bench"):
    sys.path[0] = ROOT
# The faults patch the program before run.main imports it.
sys.path.insert(1, os.path.join(ROOT, "src"))

from bench import faults  # noqa: E402
from bench import run as brun  # noqa: E402


def reading(workload: str, kind: str, variant: str | None, seed: int,
            seconds: float, rehearse: bool = False) -> dict:
    """One run of ``workload`` with ``variant`` planted: its result line."""
    argv = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", "0"] + (["--rehearse"] if rehearse
                                             else [])
    out = io.StringIO()
    with faults.planted(variant, kind), contextlib.redirect_stdout(out):
        code = brun.main(argv)
    if code != 0:
        raise RuntimeError(f"run exited {code}")
    return json.loads(out.getvalue().strip().splitlines()[-1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variant", required=True,
                    help="the control or a fault, as faults.py names them")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    _, _, mix, _, _ = brun.open_cell(args.workload)
    for seed in args.seeds:
        line = reading(args.workload, mix["kind"], args.variant, seed,
                       args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "variant": args.variant,
                          "correct": line["correct"],
                          "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
