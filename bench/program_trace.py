"""The program's own spans and named scopes in a traced run.

The program writes host spans named ``detlsh.*`` (``repro.tracing``) with
their args: ``detlsh.search.dispatch`` around each search's call into the
engine, ``detlsh.serve.batch`` around each batch of the serving runtime.
It also names device work with ``jax.named_scope`` (``fold`` in the fused
search's round loop, ``assemble`` in the build).  A scope reaches the
trace as the ``tf_op`` stat of each op's event metadata on the device
plane (``jit(while)/while/body/fold/slice:``).  JAX's ``ProfileData``
gives events without their metadata's stats, so :func:`op_scopes` reads
the ``XSpace`` protobuf's event metadata itself, skipping the event lines,
and joins them to the events by their HLO text.

:func:`load` keeps, from the newest ``.xplane.pb`` under the run's trace
directory (``<checkout>/.bench_trace``, where ``run.py`` traces), on the
host clock in ns:

  spans   every host event named ``detlsh.*``, with its args;
  ops     the ``XLA Ops`` events of each TPU plane, with their HLO name and
          scope path (``jit(while)/while/body/fold/slice``; empty when the
          op has none).

:class:`ProgramTrace` clips both to the measured window (the harness's
``bench.window`` span, on the same clock) and answers the readers'
questions.  A trace of a program without these spans or scopes reads as
nothing found, never as zero.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import json
import os
import re
from typing import Dict, List, Optional, Tuple

from bench.trace import Trace, _union, op_name

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
SPAN_PREFIX = "detlsh."
SCOPE_STAT = "tf_op"
_TPU_PLANE = re.compile(r"^/device:TPU:\d+$")


# ---------------------------------------------------------------------------
# The XSpace protobuf, as far as the op metadata goes
# ---------------------------------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """(field number, value) of one message: an int for a varint or fixed
    field, a (start, end) range for a length-delimited one."""
    while i < end:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = (i, i + size), i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = int.from_bytes(buf[i:i + size], "little"), i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield number, value


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_value(buf: bytes, entry: Tuple[int, int]) -> Optional[Tuple[int, int]]:
    """The value message of a protobuf map entry (field 2)."""
    for number, value in _fields(buf, *entry):
        if number == 2:
            return value
    return None


def op_scopes(buf: bytes) -> Dict[str, Dict[str, str]]:
    """{TPU plane name: {op's HLO text: scope path}} from a serialized
    ``XSpace``.  XPlane: name 2, event_metadata 4 and stat_metadata 5
    (maps); XEventMetadata: name 2, stats 5; XStatMetadata: id 1, name 2;
    XStat: metadata_id 1, str_value 5, ref_value 7 (a stat_metadata id
    whose name is the string)."""
    out: Dict[str, Dict[str, str]] = {}
    for number, plane in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for pn, pv in _fields(buf, *plane):
            if pn == 2:
                name = _text(buf, pv)
            elif pn == 4:
                events.append(pv)
            elif pn == 5:
                md = _map_value(buf, pv)
                if md is not None:
                    sid, sname = 0, ""
                    for sn, sv in _fields(buf, *md):
                        if sn == 1:
                            sid = sv
                        elif sn == 2:
                            sname = _text(buf, sv)
                    stat_names[sid] = sname
        if not _TPU_PLANE.match(name):
            continue
        scope_ids = {k for k, v in stat_names.items() if v == SCOPE_STAT}
        scopes = out.setdefault(name, {})
        for entry in events:
            md = _map_value(buf, entry)
            if md is None:
                continue
            ev_name, scope = "", ""
            for en, ev in _fields(buf, *md):
                if en == 2:
                    ev_name = _text(buf, ev)
                elif en == 5:
                    stat = dict(_fields(buf, *ev))
                    if stat.get(1) not in scope_ids:
                        continue
                    if 5 in stat:
                        scope = _text(buf, stat[5])
                    elif 7 in stat:
                        scope = stat_names.get(stat[7], "")
            if scope:
                scopes[ev_name] = scope.rsplit(":", 1)[0]
    return out


# ---------------------------------------------------------------------------
# Loading
# ---------------------------------------------------------------------------

def newest_xplane(trace_dir: str = TRACE_DIR) -> Optional[str]:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


@functools.lru_cache(maxsize=1)
def _load_file(path: str, mtime: float) -> dict:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        scopes = op_scopes(f.read())
    pd = ProfileData.from_file(path)
    raw: dict = {"devices": [], "spans": [], "ops": []}
    for plane in pd.planes:
        if _TPU_PLANE.match(plane.name):
            raw["devices"].append(plane.name)
            names = scopes.get(plane.name, {})
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    raw["ops"].append([plane.name, ev.start_ns, ev.end_ns,
                                       op_name(ev.name),
                                       names.get(ev.name, "")])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        raw["spans"].append([ev.start_ns, ev.end_ns, ev.name,
                                             dict(ev.stats)])
    return raw


def load(trace_dir: str = TRACE_DIR) -> Optional[dict]:
    """Spans and scoped ops of the newest trace under ``trace_dir``, as
    the plain dict :class:`ProgramTrace` is built from; None when there is
    no trace.  Parsed once per file (path and mtime)."""
    path = newest_xplane(trace_dir)
    if path is None:
        return None
    return _load_file(path, os.path.getmtime(path))


# ---------------------------------------------------------------------------
# Reduction
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ProgramTrace:
    """Program spans and device ops clipped to the window [start, end]."""

    start: float
    end: float
    spans: List[Tuple[float, float, str, dict]]
    ops: Trace          # device ops named by their scope path

    @classmethod
    def from_dict(cls, raw: dict, start: float, end: float) -> "ProgramTrace":
        spans = [(max(s, start), min(e, end), name, args)
                 for s, e, name, args in raw["spans"]
                 if start <= s < end]
        ops = Trace.from_dict({
            "devices": raw["devices"],
            "host_spans": [[start, end, "bench.window"]],
            "device_ops": [[dev, s, e, scope]
                           for dev, s, e, _, scope in raw["ops"]]})
        return cls(start=start, end=end, spans=spans, ops=ops)

    @classmethod
    def from_file(cls, path: str) -> "ProgramTrace":
        with open(path) as f:
            raw = json.load(f)
        return cls.from_dict(raw, *raw["window"])

    @property
    def window_s(self) -> float:
        return (self.end - self.start) / 1e9

    def named(self, name: str) -> List[Tuple[float, float, str, dict]]:
        return [s for s in self.spans if s[2] == name]

    def scope_seconds(self, scope: str) -> Optional[float]:
        """Device self seconds of the ops whose scope path has ``scope`` as
        a component, averaged over the devices; None when no op has it."""
        pattern = rf"(^|/){re.escape(scope)}(/|$)"
        if self.ops.op_count(pattern) == 0:
            return None
        return self.ops.op_seconds(pattern)

    def idle_inside(self, name: str) -> Optional[float]:
        """Seconds of the window in which no device op runs and the host
        is inside a span ``name``; None when there is no such span."""
        spans = _union([(s, e) for s, e, _, _ in self.named(name)])
        if not spans:
            return None
        busy = _union([(o.start, o.end) for o in self.ops.ops])
        idle, j = 0.0, 0
        for s, e in spans:
            idle += e - s
            while j < len(busy) and busy[j][1] <= s:
                j += 1
            k = j
            while k < len(busy) and busy[k][0] < e:
                idle -= min(e, busy[k][1]) - max(s, busy[k][0])
                k += 1
        return idle / 1e9


def for_window(trace: Optional[Trace],
               trace_dir: str = TRACE_DIR) -> Optional[ProgramTrace]:
    """The program's trace over the harness's window (``ctx.trace``);
    None when the run was not traced."""
    if trace is None:
        return None
    raw = load(trace_dir)
    return None if raw is None else ProgramTrace.from_dict(
        raw, trace.start, trace.end)
