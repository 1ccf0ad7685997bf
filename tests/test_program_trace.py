"""The benchmark's reduction of the program's spans and named scopes
(``bench/program_trace.py``) and the five per-layer readers built on it.

Three traces: a hand-made one whose numbers are worked out below, a small
one recorded on a TPU v5e chip (``bench/testdata/program_trace_small.json``,
its numbers worked out beside it), and one recorded here on the CPU to
drive the loader over a real ``.xplane.pb``.  A trace of a program without
the spans and scopes reads as nothing found.
"""

import importlib.util
import json
import math
import os
import types

import jax
import pytest
from jax.profiler import TraceAnnotation

from bench import program_trace
from bench.program_trace import ProgramTrace
from repro import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTDATA = os.path.join(ROOT, "bench", "testdata")
READERS = ("search.lower_ms_per_batch", "idle_share.batch_dispatch",
           "search.fold_gather_ms_per_batch", "build.assemble_ms_per_build",
           "serve.queue_wait_ms_per_query")
DEV = "/device:TPU:0"


def _reader(name, monkeypatch, pt):
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"test_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "for_window", lambda trace: pt)
    return mod.read


def _read_all(monkeypatch, pt, counters):
    ctx = types.SimpleNamespace(trace=object(), counters=counters)
    return {name: _reader(name, monkeypatch, pt)(ctx) for name in READERS}


def _hand_raw():
    # Window 0-100 us (ns below).  Dispatch spans 10-40 and 60-70 (and one
    # that starts before the window, left out).  Ops: a fold gather 5-20,
    # an assemble gather 30-35, a while op 50-90 holding a fold squeeze
    # 55-65, an op of scope "unfolded" 92-94 (not "fold"), a fold op
    # 95-120 clipped to 95-100.
    us = 1_000
    args = dict.fromkeys(tracing.ARGS, 0)
    return {"window": [0, 100 * us], "devices": [DEV],
            "spans": [
                [-10 * us, 5 * us, "detlsh.search.dispatch",
                 dict(args, trace_ms=100.0)],
                [10 * us, 40 * us, "detlsh.search.dispatch",
                 dict(args, trace_ms=1.0, lower_ms=2.0, compile_ms=3.0)],
                [60 * us, 70 * us, "detlsh.search.dispatch",
                 dict(args, trace_ms=0.5, lower_ms=1.5, compile_ms=4.0,
                      gc_ms=9.0)],
                [41 * us, 49 * us, "detlsh.serve.batch",
                 {"queries": 3, "wait_ms_sum": 30.0, "wait_ms_max": 20.0}],
                [71 * us, 79 * us, "detlsh.serve.batch",
                 {"queries": 1, "wait_ms_sum": 2.0, "wait_ms_max": 2.0}]],
            "ops": [
                [DEV, 5 * us, 20 * us, "fusion.1",
                 "jit(while)/while/body/fold/jit(_take)/gather"],
                [DEV, 30 * us, 35 * us, "fusion", "jit(f)/assemble/gather"],
                [DEV, 50 * us, 90 * us, "while.1", "jit(while)/while"],
                [DEV, 55 * us, 65 * us, "fusion.2",
                 "jit(while)/while/body/fold/squeeze"],
                [DEV, 92 * us, 94 * us, "fusion.3", "jit(g)/unfolded/add"],
                [DEV, 95 * us, 120 * us, "fusion.4",
                 "jit(while)/while/body/fold/slice"]]}


def test_hand_trace(monkeypatch):
    raw = _hand_raw()
    pt = ProgramTrace.from_dict(raw, *raw["window"])
    assert math.isclose(pt.window_s, 100e-6)
    assert len(pt.named("detlsh.search.dispatch")) == 2
    # fold: 15 + 10 + 5 (clipped) us; the while op's own 30 us and the
    # "unfolded" op are not fold
    assert math.isclose(pt.scope_seconds("fold"), 30e-6)
    assert math.isclose(pt.scope_seconds("assemble"), 5e-6)
    assert pt.scope_seconds("round") is None
    # inside 10-40 the device runs 10-20 and 30-35: idle 20-30 and 35-40;
    # 60-70 lies inside the while op
    assert math.isclose(pt.idle_inside("detlsh.search.dispatch"), 15e-6)
    assert pt.idle_inside("detlsh.nothing") is None
    got = _read_all(monkeypatch, pt, {"batches": 2, "builds": 1})
    want = {"search.lower_ms_per_batch": (6.0 + 6.0) / 2,
            "idle_share.batch_dispatch": 15.0,
            "search.fold_gather_ms_per_batch": 30e-3 / 2,
            "build.assemble_ms_per_build": 5e-3,
            "serve.queue_wait_ms_per_query": 32.0 / 4}
    for name, value in want.items():
        assert math.isclose(got[name], value, rel_tol=1e-9), name


def test_recorded_chip_trace(monkeypatch):
    with open(os.path.join(TESTDATA,
                           "program_trace_small.expected.json")) as f:
        want = json.load(f)
    pt = ProgramTrace.from_file(os.path.join(TESTDATA,
                                             "program_trace_small.json"))
    assert math.isclose(pt.window_s, want["values"]["window_s"])
    got = _read_all(monkeypatch, pt, want["counters"])
    for name in READERS:
        assert math.isclose(got[name], want["values"][name],
                            rel_tol=1e-9), name
    assert len(pt.named("detlsh.search.dispatch")) == 3
    # the fold's ops all lie inside the fused round loop's while body
    fold = [o for o in pt.ops.ops if "/fold/" in o.name]
    assert len(fold) == 182
    assert all(o.name.startswith("jit(while)/while/body/fold/")
               for o in fold)


def test_trace_without_spans_or_scopes_reads_nothing(monkeypatch):
    raw = _hand_raw()
    raw["spans"] = []
    raw["ops"] = [o[:4] + [""] for o in raw["ops"]]
    pt = ProgramTrace.from_dict(raw, *raw["window"])
    got = _read_all(monkeypatch, pt, {"batches": 2, "builds": 1})
    assert got == dict.fromkeys(READERS)
    assert _read_all(monkeypatch, None, {"batches": 2}) == dict.fromkeys(
        READERS)
    assert program_trace.for_window(None) is None


def test_no_trace_directory_reads_nothing(tmp_path):
    window = types.SimpleNamespace(start=0, end=1)
    assert program_trace.load(str(tmp_path)) is None
    assert program_trace.for_window(window, str(tmp_path)) is None


def _varint(v):
    out = bytearray()
    while True:
        b, v = v & 0x7F, v >> 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _plane(name, stat_names, events):
    body = _field(2, name)
    for sid, sname in stat_names.items():
        body += _field(5, _field(1, sid) + _field(2, _field(1, sid)
                                                  + _field(2, sname)))
    for eid, (ev_name, stats) in events.items():
        md = _field(1, eid) + _field(2, ev_name)
        for stat in stats:
            md += _field(5, stat)
        body += _field(4, _field(1, eid) + _field(2, md))
    return _field(1, body)


def test_op_scopes_reads_the_event_metadata():
    tf_op = 7
    space = _plane("/device:TPU:0", {tf_op: "tf_op", 8: "flops",
                                     9: "jit(h)/assemble/gather:"}, {
        1: ("%fusion.1 = f32[8] fusion()",
            [_field(1, 8) + _field(3, 100),
             _field(1, tf_op) + _field(5, "jit(w)/while/body/fold/gather:")]),
        2: ("%gather.2 = f32[8] gather()",       # interned: a ref_value
            [_field(1, tf_op) + _field(7, 9)]),
        3: ("%copy.3 = f32[8] copy()", [_field(1, 8) + _field(3, 5)]),
    }) + _plane("/host:CPU", {tf_op: "tf_op"}, {
        1: ("detlsh.search.dispatch", [_field(1, tf_op) + _field(5, "x:")]),
    })
    assert program_trace.op_scopes(space) == {"/device:TPU:0": {
        "%fusion.1 = f32[8] fusion()": "jit(w)/while/body/fold/gather",
        "%gather.2 = f32[8] gather()": "jit(h)/assemble/gather"}}


def test_load_reads_the_program_spans_of_a_recorded_trace(tmp_path):
    d = str(tmp_path / "trace")
    jax.profiler.start_trace(d)
    try:
        with TraceAnnotation("bench.window") as _:
            with tracing.span("detlsh.serve.batch") as s:
                s.set(queries=2, wait_ms_sum=3.5)
            with tracing.span("detlsh.search.dispatch", batch=4):
                jax.jit(lambda x: x * 2.0)(jax.numpy.ones(3))
    finally:
        jax.profiler.stop_trace()
    raw = program_trace.load(d)
    assert program_trace.load(d) is raw           # parsed once per file
    assert raw["devices"] == [] and raw["ops"] == []     # no TPU here
    names = [s[2] for s in raw["spans"]]
    assert names == ["detlsh.serve.batch", "detlsh.search.dispatch"]
    start = min(s[0] for s in raw["spans"])
    end = max(s[1] for s in raw["spans"])
    pt = program_trace.for_window(types.SimpleNamespace(start=start,
                                                        end=end + 1), d)
    [(_, _, _, args)] = pt.named("detlsh.serve.batch")
    assert args["queries"] == 2 and args["wait_ms_sum"] == 3.5
    [(_, _, _, args)] = pt.named("detlsh.search.dispatch")
    assert args["batch"] == 4 and args["compiles"] >= 1
    later = program_trace.for_window(
        types.SimpleNamespace(start=end + 1, end=end + 2), d)
    assert later.named("detlsh.search.dispatch") == []


@pytest.mark.parametrize("name", READERS)
def test_every_new_metric_is_declared_for_its_cells(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    [entry] = [m for m in bench["per_layer"] if m["name"] == name]
    cells = {w["name"]: w for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert entry["workloads"] and set(entry["workloads"]) <= set(cells)
    for cell in entry["workloads"]:
        assert cell in e2e[entry["moves"]].get("workloads", [cell])
