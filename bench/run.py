"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload sift1m.batch64 --seed 7 --seconds 10 \
        --trace 0

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Everything it
needs is found by name: the configuration's file (``configs``), the traffic
mix ``mixes/<traffic>.json`` (data whose ``kind`` names the generator's
module ``kinds/<kind>.py``, loaded by ``traffic.py``), and one reader
``metrics/<name>.py`` per per-layer metric.  A new cell, configuration or
metric is new files and new entries; no file here changes.

A run makes its data from ``--seed`` on the device, builds the index and
warms every shape the cell's traffic uses (set-up), measures for
``--seconds``, then reads the peak memory, frees the program's state and
compares what the timed path returned with the plain reference
(``reference.py``).  With ``--trace 1`` the window is traced and the line
carries the per-layer metrics instead of the end-to-end ones.  The last
stdout line is one JSON object; the last stderr lines are each number the
comparison used beside its limit.

It needs a TPU: on any other platform, or with fewer chips than the cell
asks for, it exits non-zero and names what JAX found.  ``--rehearse``
runs the cell at a tiny size on whatever JAX finds (the CPU here) to test
the harness; it prints counts and the comparison, never a device metric.
"""

from __future__ import annotations

import os
import sys
import time

T_START = time.time()
# Run as a script, this directory leads sys.path, where its module names
# (trace, data) would shadow others: put the checkout's root there instead.
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if sys.path and os.path.abspath(sys.path[0]) == os.path.join(ROOT, "bench"):
    sys.path[0] = ROOT

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402

BENCH = os.path.join(ROOT, "bench")
SRC = os.path.join(ROOT, "src")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")

# Sizes of a rehearsal: the cell's shape, cut until a CPU runs it in a
# minute.
REHEARSAL = {"n": 4096, "n_queries": 512, "clusters": 16, "rate_qps": 20.0,
             "check_queries": 16}


def process_start() -> float:
    """Wall-clock time this process started (Linux), else import time."""
    try:
        with open("/proc/self/stat") as f:
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(float(line.split()[1]) for line in f
                         if line.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return T_START


def fail(msg: str, code: int = 2) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_entries(bench: dict, name: str):
    """(workload, configuration entry, end-to-end metrics, per-layer
    metrics) of one cell, as BENCHMARK.json lists them."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        fail(f"unknown workload {name!r} (known: {sorted(cells)})")
    cell = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in moved)]
    return cell, config, e2e, layer


def open_cell(workload: str, rehearse: bool = False):
    """(cell, configuration, mix, end-to-end metrics, per-layer metrics)
    of a workload, from BENCHMARK.json and the files it names; a
    rehearsal cuts the sizes to ``REHEARSAL``."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, e2e, layer = cell_entries(bench, workload)
    cfg = load_json(os.path.join(ROOT, config["file"]))
    mix = load_json(os.path.join(BENCH, "mixes", cell["traffic"] + ".json"))
    if rehearse:
        cfg = dict(cfg, n=REHEARSAL["n"], n_queries=REHEARSAL["n_queries"],
                   data=dict(cfg["data"], clusters=REHEARSAL["clusters"]))
        mix = {k: REHEARSAL.get(k, v) for k, v in mix.items()}
    return cell, cfg, mix, e2e, layer


def start_jax(what: str, chips: int, rehearse: bool = False):
    """Import the program and JAX; refuse anything but ``chips`` TPU chips
    (a rehearsal takes what it finds), and keep compiled programs in the
    checkout's persistent cache.  Returns the devices."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"the system under test is missing ({SRC}/repro): run from a "
             f"checkout of the repository")
    sys.path.insert(0, SRC)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    dev = devices[0]
    if rehearse:
        return devices
    if dev.platform != "tpu" or len(devices) < chips:
        fail(f"{what} needs {chips} TPU chip(s); JAX found platform "
             f"{dev.platform!r} ({dev.device_kind}) x{len(devices)}")
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return devices


def load_reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Backend compiles and persistent-cache loads, counted while
    ``active``.  JAX reports a load from the persistent cache as a
    compile too, so ``compiles - cache_loads`` is what really compiled."""

    def __init__(self):
        import jax
        self.active = False
        self.compiles = 0
        self.cache_loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if self.active and event == "/jax/compilation_cache/cache_hits":
            self.cache_loads += 1


class Run:
    """What one run shares with its traffic: sizes, data and keys.

    The build key comes from the configuration's ``build_seed``, not from
    the run's seed: the key sets how many candidates each query admits, so
    every seed builds with the same key and does the same work on a fresh
    draw of the data."""

    def __init__(self, cfg: dict, seed: int, data, queries):
        import numpy as np
        from bench.data import seed_key
        self.cfg = cfg
        self.seed = seed
        self.k = cfg["k"]
        self.data = data
        self.queries_host = np.asarray(queries)
        self.build_key = seed_key(cfg["build_seed"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any platform; no device metrics")
    args = ap.parse_args(argv)
    t_proc = process_start()

    cell, cfg, mix, e2e, layer = open_cell(args.workload, args.rehearse)
    devices = start_jax(args.workload, cell["chips"], args.rehearse)
    import jax
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    peak = None
    if not args.rehearse and args.trace:
        from bench.roofline import peaks
        peak = peaks(dev.device_kind)
    counter = CompileCounter()

    from bench import data as bdata
    from bench import traffic
    base, queries = bdata.make(cfg, args.seed)
    jax.block_until_ready(base)
    run = Run(cfg, args.seed, base, queries)
    work = traffic.make(mix["kind"], run, mix)
    work.setup()
    # What set-up left behind stays out of the collector's scans in the
    # window (a full collection over the compiled programs' objects stalls
    # the host); it is released again before the check.
    gc.collect()
    gc.freeze()
    setup_s = time.time() - t_proc

    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    counter.active = True
    with traffic.span("bench.window"):
        out = work.window(args.seconds)
    counter.active = False
    if args.trace:
        jax.profiler.stop_trace()
    stats = dev.memory_stats() or {}
    memory_peak = stats.get("peak_bytes_in_use")
    print(f"bench: window {out['elapsed_s']:.3f}s; inside it "
          f"{counter.compiles - counter.cache_loads} compiles and "
          f"{counter.cache_loads} loads from the persistent compile cache",
          file=sys.stderr)
    if "late_p99_ms" in out:
        print(f"bench: generator lateness p99 {out['late_p99_ms']:.3f} ms, "
              f"max {out['late_max_ms']:.3f} ms; served "
              f"{out['served_qps']:.2f} queries/s", file=sys.stderr)

    gc.unfreeze()
    work.release()
    gc.collect()
    numbers = work.check()
    limits = dict(cfg["limits"], **mix.get("limits", {}))
    checks = {}
    for name, limit in limits.items():
        if name in numbers:
            checks[name] = {"value": numbers[name], "limit": limit}
    correct = bool(checks) and all(c["value"] <= c["limit"]
                                   for c in checks.values())

    metrics = {}
    if not args.rehearse:
        if not args.trace:
            values = dict(out, setup_s=setup_s,
                          recall_at_10=1.0 - numbers.get("miss_rate", 1.0))
            for m in e2e:
                if values.get(m["name"]) is not None:
                    metrics[m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
        else:
            from bench.trace import Trace, load
            tr = Trace.from_dict(load(TRACE_DIR))
            ctx = Context(tr, work.counters, peak, cfg)
            for m in layer:
                v = load_reader(m["name"])(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            device.update(busy_s=tr.busy_s(), window_s=tr.window_s)
            breakdown = {"device_ops": tr.top_ops(), "idle_gaps":
                         tr.idle_gaps()}
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["memory_peak_bytes"] = memory_peak

    for name, c in checks.items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} <= {c['limit']!r} {ok}",
              file=sys.stderr)
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"]}
    if args.rehearse:
        result["rehearsal"] = {"counters": {k: v for k, v in
                                            work.counters.items()
                                            if isinstance(v, (int, float))},
                               "numbers": {k: v for k, v in numbers.items()
                                           if k not in checks}}
    else:
        result["metrics"] = metrics
        result["device"] = device
        if args.trace:
            result["breakdown"] = breakdown
    result["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


class Context:
    """What a per-layer reader may read: the traced window, the traffic's
    program counters, the chip's peaks and the configuration."""

    def __init__(self, trace, counters: dict, peak: dict, cfg: dict):
        self.trace = trace
        self.counters = counters
        self.peak = peak
        self.cfg = cfg


if __name__ == "__main__":
    sys.exit(main())
