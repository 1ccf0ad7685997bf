"""Self-check of the benchmark's own arithmetic, on the CPU.

    python3 bench/selfcheck.py

1. The trace reduction (``trace.py``) on a hand-made trace whose busy
   time, idle gaps and kernel times are worked out below, and on a small
   trace recorded on a v5e chip (``testdata/``), whose numbers must come
   out as they did when it was recorded.
2. The roofline work functions against hand-computed values, and the peak
   table's refusal of an unknown ``device_kind``.
3. One tiny cell end to end on the CPU through the rehearsal path, which
   must be correct and must print no device metric.

Exits non-zero on the first failed check.
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if sys.path and os.path.abspath(sys.path[0]) == os.path.join(ROOT, "bench"):
    sys.path[0] = ROOT

from bench import roofline  # noqa: E402
from bench.trace import Trace  # noqa: E402

TESTDATA = os.path.join(ROOT, "bench", "testdata")


def close(a: float, b: float, rel: float = 1e-9) -> bool:
    return math.isclose(a, b, rel_tol=rel, abs_tol=1e-12)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"selfcheck FAILED: {what}")
    print(f"ok  {what}")


def hand_trace() -> None:
    # Window 0-100 us.  A while op (5-45) holds fusion.1 (10-30) and a
    # range_rerank call (30-40); a second call (95-120) is clipped to
    # 95-100; fusion.2 (50-60) runs in another program; one op lies
    # outside the window.  Times in ns.
    dev = "/device:TPU:0"
    raw = {"devices": [dev],
           "host_spans": [[0, 100_000, "bench.window"],
                          [40_000, 50_000, "bench.search"],
                          [60_000, 95_000, "bench.readback"]],
           "modules": [[dev, 5_000, 45_000, "jit_while"],
                       [dev, 50_000, 60_000, "jit_top_k"]],
           "device_ops": [[dev, 5_000, 45_000, "while.2"],
                          [dev, 10_000, 30_000, "fusion.1"],
                          [dev, 30_000, 40_000, "range_rerank.3"],
                          [dev, 50_000, 60_000, "fusion.2"],
                          [dev, 95_000, 120_000, "range_rerank.3"],
                          [dev, 150_000, 160_000, "fusion.3"]]}
    tr = Trace.from_dict(raw)
    rr = r"^range_rerank(\.\d+)?$"
    check(close(tr.window_s, 100e-6), "hand trace: window 100 us")
    check(close(tr.busy_s(), 55e-6), "hand trace: busy = union 5-45, "
          "50-60, 95-100 = 55 us")
    check(close(tr.op_seconds(rr), 15e-6),
          "hand trace: kernel self time 10 + 5 (clipped) us")
    check(close(tr.op_seconds(exclude=rr), 40e-6),
          "hand trace: other self time: while 40-20-10, fusions 20 + 10")
    top = tr.top_ops(5)
    check([t[0] for t in top] == [
        "jit_while/fusion.1", "jit_while/while.2",
        "jit_while/range_rerank.3", "jit_top_k/fusion.2", "range_rerank.3"]
        and [round(t[1] * 1e6, 9) for t in top] == [20, 10, 10, 10, 5],
        "hand trace: top ops by program/op, self time")
    gaps = tr.idle_gaps()
    check([g[0] for g in gaps] == ["bench.readback", "host", "bench.search"]
          and [round(g[1] * 1e6, 9) for g in gaps] == [35, 5, 5],
          "hand trace: gaps 60-95 (bench.readback), 0-5 (no span), 45-50 "
          "(bench.search)")


def recorded_trace() -> None:
    with open(os.path.join(TESTDATA, "trace_small.expected.json")) as f:
        want = json.load(f)
    tr = Trace.from_file(os.path.join(TESTDATA, "trace_small.json"))
    rr = want["patterns"]["range_rerank_s"]
    got = {"window_s": tr.window_s, "busy_s": tr.busy_s(),
           "range_rerank_s": tr.op_seconds(rr),
           "other_s": tr.op_seconds(exclude=rr),
           "while_s": tr.op_seconds(want["patterns"]["while_s"])}
    for name, value in want["values"].items():
        check(close(got[name], value), f"recorded trace: {name} = {value!r}")
    check(tr.op_count(rr) == want["range_rerank_calls"] == 2,
          "recorded trace: one range_rerank call per batch (one round)")
    check(close(got["range_rerank_s"] + got["other_s"], got["busy_s"]),
          "recorded trace: self times add up to the busy time (one stream, "
          "the while op's body nested in it)")


def work_functions() -> None:
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # 64 queries x 100,000 candidates, d = 128: 2*128*6.4e6 = 1.6384e9
    # operations -> 8.3168e-6 s; 4*128*1e5 = 5.12e7 bytes -> 6.2515e-5 s.
    got = roofline.range_rerank_floor([100_000] * 64, 128, peak)
    check(close(got, 5.12e7 / 819e9), "range_rerank floor: bytes bound, "
          "62.515 us")
    # one candidate set of 10^7 at d = 96: 1.92e9 ops (9.746e-6 s) vs
    # 3.84e9 bytes (4.689e-3 s).
    got = roofline.range_rerank_floor([10_000_000], 96, peak)
    check(close(got, 3.84e9 / 819e9), "range_rerank floor at d=96")
    # n = 1e6, K = 4, L = 8: 4*32e6 + 4*32e6 + 8*8e6 = 3.2e8 bytes.
    got = roofline.encode_pack_floor(1_000_000, 4, 8, peak)
    check(close(got, 3.2e8 / 819e9), "encode_pack floor: 390.72 us")
    try:
        roofline.peaks("TPU v99 imaginary")
    except KeyError:
        check(True, "peak table refuses an unknown device kind")
    else:
        check(False, "peak table refuses an unknown device kind")
    check(roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9,
          "peak table: v5e 819 GB/s")


def rehearsal() -> None:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         "sift1m.batch64", "--seed", str(2 ** 31 + 9), "--seconds", "2",
         "--trace", "0", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=600, check=False)
    check(out.returncode == 0, "rehearsal exits 0" + (
        "" if out.returncode == 0 else f": {out.stderr[-2000:]}"))
    line = json.loads(out.stdout.strip().splitlines()[-1])
    check(line["correct"] is True, "rehearsal of sift1m.batch64 is correct")
    check("metrics" not in line and "device" not in line,
          "rehearsal prints no device metric")
    off = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         "sift1m.batch64", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=600, check=False)
    check(off.returncode != 0 and "'cpu'" in off.stderr
          and not off.stdout.strip(),
          "off the TPU a run exits non-zero, names the platform, prints "
          "no result")


def main() -> int:
    hand_trace()
    recorded_trace()
    work_functions()
    rehearsal()
    print("selfcheck passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
