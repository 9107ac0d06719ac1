"""The name-stack and program-span reading of ``xplane_scopes.py``, and the
reduction of ``xplane.py`` it must leave as it is.

On the one-chip v5e trace kept under ``tests/data``: each op's ``tf_op``
path, read with the standard library, against TensorFlow's own parser of
the same bytes where TensorFlow is installed; a scope's self time against
the op's.  On hand-made traces: that a program span changes neither the
traced window nor its idle gaps.  And the recorded trace's ``Summary``
and ``breakdown()``, pinned to the values they read today.
"""

import re
from pathlib import Path

import pytest

import tiny_cells  # noqa: F401  (puts bench/ on the import path)
import xplane
import xplane_scopes

TRACE = Path(__file__).resolve().parent / "data" / "probe-1chip.xplane.pb.gz"
TPU0 = "/device:TPU:0"


# -- a hand-made serialized XSpace ------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _message(*fields) -> bytes:
    return b"".join(_field(n, v) for n, v in fields)


def _plane(name, ops, stat_names):
    """A plane with a line to skip, event metadata ``{id: (op name, the
    fields of its one stat)}`` and stat metadata ``{id: name}``."""
    parts = [_field(1, 3), _field(2, name),
             _field(3, _message((2, "XLA Ops"), (4, _message((1, 1)))))]
    for key, (op, stats) in ops.items():
        meta = _message((1, key), (2, op), (5, _message(*stats)))
        parts.append(_field(4, _message((1, key), (2, meta))))
    for key, sname in stat_names.items():
        parts.append(_field(5, _message((1, key), (2, _message((1, key),
                                                                (2, sname))))))
    return b"".join(parts)


def test_op_paths_of_a_hand_made_xspace():
    stats = {7: "tf_op", 8: "flops", 9: "jit(f)/eval_probe/dot_general:"}
    device = _plane("/device:TPU:0", {
        1: ("%fusion.3 = f32[8] fusion()",
            [(1, 8), (4, 100)]),                       # no tf_op
        2: ("%fusion.4 = f32[8] fusion()",
            [(1, 7), (5, "jit(f)/while/body/merge/add:")]),
        3: ("%dot.1 = f32[8] dot()", [(1, 7), (7, 9)]),  # by reference
        4: ("%copy.2 = f32[8] copy()", [(1, 7), (5, "jit(f)/copy")]),
    }, stats)
    host = _plane("/host:CPU", {1: ("serve.flush", [(1, 7), (5, "x")])},
                  {7: "tf_op"})
    xspace = _field(1, device) + _field(1, host) + _field(4, "localhost")
    assert xplane_scopes.op_paths(xspace) == {TPU0: {
        "%fusion.4 = f32[8] fusion()": "jit(f)/while/body/merge/add",
        "%dot.1 = f32[8] dot()": "jit(f)/eval_probe/dot_general",
        "%copy.2 = f32[8] copy()": "jit(f)/copy",
    }}


def test_op_paths_of_the_recorded_trace():
    paths = xplane_scopes.op_paths(xplane_scopes.read_bytes(TRACE))
    assert list(paths) == [TPU0]
    by_op = {op.split(" = ", 1)[0]: p for op, p in paths[TPU0].items()}
    assert by_op["%fusion.10"] == "jit(body)/while/body/closed_call/dot_general"
    assert by_op["%vq_window.7"] == (
        "jit(body)/while/body/closed_call/vq_window/pallas_call")


def test_op_paths_match_tensorflows_parser():
    pb2 = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    raw = xplane_scopes.read_bytes(TRACE)
    space = pb2.XSpace()
    space.ParseFromString(raw)
    want = {}
    for plane in space.planes:
        if not re.match(r"^/device:TPU:\d+$", plane.name):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        ops = want.setdefault(plane.name, {})
        for meta in plane.event_metadata.values():
            for stat in meta.stats:
                if names.get(stat.metadata_id) != "tf_op":
                    continue
                path = (stat.str_value
                        if stat.WhichOneof("value") == "str_value"
                        else names.get(stat.ref_value))
                if path:
                    ops[meta.name] = re.sub(r":[^/]*$", "", path)
    assert xplane_scopes.op_paths(raw) == want


def test_scope_self_time_of_the_recorded_trace():
    raw = xplane_scopes.read_bytes(TRACE)
    profile = xplane.read_profile(TRACE)
    scopes = xplane_scopes.scope_self_s(
        profile, xplane_scopes.op_paths(raw))
    summary = xplane.summarize(profile)
    assert scopes["vq_window"] == pytest.approx(
        summary.devices[0].op_self_s["vq_window"])
    # an op counts once under each scope of its path, so no scope holds
    # more than the chip's busy time
    assert 0 < max(scopes.values()) <= summary.devices[0].busy_s + 1e-12


# -- hand-made profiles ------------------------------------------------------

def _fake_profile(planes):
    from types import SimpleNamespace as NS

    return NS(planes=[
        NS(name=pname, lines=[
            NS(name=lname, events=[NS(start_ns=s, duration_ns=d, name=n)
                                   for s, d, n in evs])
            for lname, evs in lines.items()])
        for pname, lines in planes.items()])


PROBE = "%fusion.9 = f32[8] fusion()"
WHILE = "%while.1 = (f32[8]) while()"


def _serving_trace(program_spans):
    return _fake_profile({
        "/host:CPU": {"main": [(100, 50, "sleep"), (150, 10, "submit"),
                               (160, 40, "collect")],
                      "quantize-flush": program_spans},
        "/device:TPU:0": {"XLA Ops": [(120, 10, WHILE), (122, 4, PROBE),
                                      (170, 5, PROBE)]},
    })


def test_program_spans_leave_the_window_and_its_gaps_alone():
    bare = xplane.summarize(_serving_trace([]))
    spans = [(20, 400, "serve.idle_wait"), (125, 60, "serve.flush"),
             (130, 10, "serve.lookup"), (190, 5, "loadgen.submit")]
    traced = _serving_trace(spans)
    s = xplane.summarize(traced)
    assert (s.window_s, s.spans) == (bare.window_s, bare.spans)
    assert s.window_s == pytest.approx(100e-9)       # harness spans, ops
    assert s.devices[0].gaps == bare.devices[0].gaps
    assert s.breakdown() == bare.breakdown()
    assert xplane_scopes.program_spans(traced) == sorted(
        (t, t + d, n) for t, d, n in spans)


def test_scope_self_time_and_overlap_on_a_hand_made_trace():
    profile = _serving_trace([])
    paths = {TPU0: {WHILE: "jit(f)/while", PROBE: "jit(f)/while/eval_probe"}}
    scopes = xplane_scopes.scope_self_s(profile, paths)
    assert scopes["eval_probe"] == pytest.approx(9e-9)
    assert scopes["while"] == pytest.approx(15e-9)   # 6 of its own, 9 under
    assert scopes["jit(f)"] == pytest.approx(15e-9)
    assert xplane_scopes.scope_self_s(profile, {}) == {}
    gaps = [(100, 120), (130, 170), (175, 200)]
    flush = [(125, 185)]
    assert xplane_scopes.overlap_s(gaps, flush) == pytest.approx(50e-9)
    assert xplane_scopes.overlap_s(gaps, []) == 0.0


# -- xplane.py's reduction of the recorded trace, pinned ----------------------

def test_recorded_summary_and_breakdown_are_pinned():
    s = xplane.load(TRACE)
    assert s.window_s == 0.039811992000000004
    assert s.busy_s == 0.019804077
    assert s.idle_share() == 0.5025600075474748
    assert len(s.spans) == 5 and len(s.devices) == 1
    d = s.devices[0]
    assert (d.index, d.busy_s, d.collective_s) == (0, 0.019804077, 0.0)
    assert len(d.gaps) == 57
    assert sum(e - b for b, e in d.gaps) == 20007915.0
    assert {k: d.kernel_s[k] for k in ("vq_window", "vq_delta",
                                       "vq_assign")} == {
        "vq_window": 0.002062321000000002,
        "vq_delta": 0.00885263700000017,
        "vq_assign": 6.9910000000000005e-06}
    assert len(d.op_self_s) == 25
    assert sum(d.op_self_s.values()) == 0.019804077000000104
    assert s.breakdown() == {
        "device_ops": [
            ["vq_delta", 0.00885263700000017],
            ["multiply_subtract_fusion", 0.0047195329999999485],
            ["reduce", 0.0021176309999999866],
            ["vq_window", 0.002062321000000002],
            ["fusion", 0.0008431099999999959],
            ["while", 0.0004648290000000003],
            ["multiply_reduce_fusion", 0.00041833300000000017],
            ["reduce_sum", 0.00013220500000000004],
            ["copy", 6.909600000000006e-05],
            ["dynamic_update_slice", 5.458000000000011e-05]],
        "idle_gaps": [
            ["sleep", 0.012992756000000001],
            ["collect", 0.002806199],
            ["collect", 0.002437537],
            ["chunk", 0.0017568960000000002],
            ["chunk", 3.507e-06],
            ["chunk", 3.428e-06],
            ["chunk", 1.6730000000000001e-06],
            ["chunk", 1.653e-06],
            ["chunk", 1.4080000000000001e-06],
            ["chunk", 1.4000000000000001e-06]]}
