"""Reduction of a profiler trace to the numbers the per-layer readers use.

A traced run wraps its measured window in a host span ``bench.window`` and
every call into the program in a host span of its own (``bench.search``,
``bench.submit``, ``bench.pump``, ``bench.build``, ...) with
``jax.profiler.TraceAnnotation``.  The profiler writes an ``.xplane.pb``;
:func:`load` keeps from it, on the host clock in ns:

  device ops   the events of the ``XLA Ops`` line of each TPU device plane,
               named by their HLO instruction (``range_rerank.5``,
               ``sort.11``, ``fusion.1``); a control-flow op (``while``)
               spans the ops of its body, which nest inside it in time;
  modules      the events of the ``XLA Modules`` line (``jit_while``,
               ``jit__fused_build_jit``), each op's enclosing program;
  host spans   every host event whose name starts with ``bench.``.

:class:`Trace` clips the device ops to the window, gives each its self time
(its duration less that of the ops nested in it, so nothing counts twice)
and answers the questions the readers ask: busy time (the union of op
intervals), self time of the ops whose name matches a pattern, the top ops
by program and name, and the longest idle gaps, each named by the
innermost host span over its midpoint.  The same reduction runs on a small
trace recorded on the chip (``testdata/``) in the self-check.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
from typing import List, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_INSTRUCTION = re.compile(r"^%?([^\s=]+)\s*=")


def op_name(event_name: str) -> str:
    """``%sort.11 = (u32[...]) sort(...)`` -> ``sort.11``."""
    m = _INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name.split(" ", 1)[0]


def load(trace_dir: str) -> dict:
    """Device ops, modules and host spans from the newest ``.xplane.pb``
    under ``trace_dir``, as the plain dict :class:`Trace` is built from."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    out: dict = {"devices": [], "device_ops": [], "modules": [],
                 "host_spans": []}
    for plane in pd.planes:
        if re.match(r"^/device:TPU:\d+$", plane.name):
            out["devices"].append(plane.name)
            for line in plane.lines:
                key = {"XLA Ops": "device_ops",
                       "XLA Modules": "modules"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    name = (op_name(ev.name) if key == "device_ops"
                            else ev.name.split("(", 1)[0])
                    out[key].append([plane.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns, name])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        out["host_spans"].append(
                            [ev.start_ns, ev.start_ns + ev.duration_ns,
                             ev.name])
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class Op:
    device: str
    start: float
    end: float
    name: str
    module: str
    self_ns: float


def _with_self_time(ops: List[Op]) -> List[Op]:
    """Each op's duration less the durations of the ops directly nested in
    it on the same device."""
    ops = sorted(ops, key=lambda o: (o.device, o.start, -o.end))
    stack: List[Op] = []
    for op in ops:
        while stack and (stack[-1].device != op.device
                         or stack[-1].end <= op.start):
            stack.pop()
        if stack and op.end <= stack[-1].end:
            stack[-1].self_ns -= op.end - op.start
        stack.append(op)
    return ops


@dataclasses.dataclass
class Trace:
    """Device ops clipped to the measured window, and the host spans."""

    start: float
    end: float
    n_devices: int
    ops: List[Op]
    spans: List[Tuple[float, float, str]]

    @classmethod
    def from_dict(cls, raw: dict) -> "Trace":
        windows = [s for s in raw["host_spans"] if s[2] == WINDOW_SPAN]
        if len(windows) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN!r} host span, "
                             f"found {len(windows)}")
        start, end, _ = windows[0]
        modules: dict = {}                     # device -> programs by start
        for dev, s, e, name in sorted(raw.get("modules", []),
                                      key=lambda m: m[1]):
            modules.setdefault(dev, []).append((s, e, name))
        starts = {dev: [m[0] for m in ms] for dev, ms in modules.items()}
        ops = []
        for dev, s, e, name in raw["device_ops"]:
            s, e = max(s, start), min(e, end)
            if e <= s:
                continue
            mid = (s + e) / 2
            i = bisect.bisect_right(starts.get(dev, []), mid) - 1
            module = ""
            if i >= 0 and modules[dev][i][1] >= mid:
                module = modules[dev][i][2]
            ops.append(Op(dev, s, e, name, module, e - s))
        spans = [tuple(s) for s in raw["host_spans"] if s[2] != WINDOW_SPAN]
        return cls(start=start, end=end,
                   n_devices=max(1, len(raw["devices"])),
                   ops=_with_self_time(ops), spans=spans)

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        with open(path) as f:
            return cls.from_dict(json.load(f))

    @property
    def window_s(self) -> float:
        return (self.end - self.start) / 1e9

    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the devices."""
        total = sum(e - s for s, e in _union([(o.start, o.end)
                                               for o in self.ops]))
        return total / 1e9 / self.n_devices

    def op_seconds(self, pattern: str = r".", *, exclude: str = "") -> float:
        """Summed self time of the ops whose name matches ``pattern`` (and
        not ``exclude``), averaged over the devices."""
        rx = re.compile(pattern)
        rxx = re.compile(exclude) if exclude else None
        total = sum(o.self_ns for o in self.ops if rx.search(o.name)
                    and not (rxx and rxx.search(o.name)))
        return total / 1e9 / self.n_devices

    def op_count(self, pattern: str) -> int:
        rx = re.compile(pattern)
        return sum(1 for o in self.ops if rx.search(o.name))

    def top_ops(self, limit: int = 10) -> List[list]:
        """Self seconds by ``program/op``, largest first."""
        acc: dict = {}
        for o in self.ops:
            key = f"{o.module}/{o.name}" if o.module else o.name
            acc[key] = acc.get(key, 0.0) + o.self_ns / 1e9 / self.n_devices
        return [[k, v] for k, v in sorted(acc.items(),
                                          key=lambda kv: -kv[1])[:limit]]

    def idle_gaps(self, limit: int = 10) -> List[list]:
        """The longest device-idle intervals in the window, each named by
        the innermost host span over its midpoint (``host`` when none)."""
        busy = _union([(o.start, o.end) for o in self.ops])
        edges = [self.start] + [x for iv in busy for x in iv] + [self.end]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:limit]:
            mid = (s + e) / 2
            over = [sp for sp in self.spans if sp[0] <= mid <= sp[1]]
            name = (min(over, key=lambda sp: sp[1] - sp[0])[2] if over
                    else "host")
            out.append([name, (e - s) / 1e9])
        return out
