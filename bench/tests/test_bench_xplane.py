"""The reduction from a profiler trace to the benchmark's numbers.

On hand-made traces (two chips, a collective, nested ops), and on a small
trace recorded on one TPU v5e and kept under ``tests/data``: the busy
time against a union taken on a nanosecond grid, the kernel time by name
and the collective time against plain sums over the raw events.
"""

import re
from pathlib import Path

import numpy as np
import pytest

import tiny_cells  # noqa: F401  (puts bench/ on the import path)
import xplane

DATA = Path(__file__).resolve().parent / "data"
TRACES = sorted(DATA.glob("*.xplane.pb.gz"))


def test_union_length_merges_overlaps_and_touching_intervals():
    total, merged = xplane.union_length([(5, 7), (0, 2), (1, 3), (3, 4),
                                         (6, 6.5)])
    assert merged == [(0, 4), (5, 7)]
    assert total == 6


def test_self_time_subtracts_directly_nested_ops():
    ops = [(0, 10, "while"), (1, 3, "a"), (4, 8, "fusion"), (5, 6, "b")]
    assert xplane.self_times(ops) == [4, 2, 3, 1]


@pytest.mark.parametrize("text, base, coll", [
    ("%vq_window.7 = f32[1024,128]{1,0} custom-call(f32[10,128] %x)",
     "vq_window", False),
    ("%all-reduce.3 = f32[1024,128]{1,0} all-reduce(f32[1024,128] %d)",
     "all-reduce", True),
    ("%all-reduce-start.1 = f32[4]{0} all-reduce-start(f32[4] %p)",
     "all-reduce-start", True),
    ("%fusion.12 = f32[8]{0} fusion(f32[8] %a), kind=kLoop", "fusion", False),
    ("%copy-start = (f32[8]) copy-start(f32[8] %w)", "copy-start", False),
])
def test_op_names(text, base, coll):
    assert xplane.op_base_name(text) == base
    assert xplane.is_collective(text) == coll


def _fake_profile(planes):
    """A stand-in for ``jax.profiler.ProfileData``: planes of named lines
    of events (start_ns, duration_ns, name)."""
    from types import SimpleNamespace as NS

    return NS(planes=[
        NS(name=pname, lines=[
            NS(name=lname, events=[NS(start_ns=s, duration_ns=d, name=n)
                                   for s, d, n in evs])
            for lname, evs in lines.items()])
        for pname, lines in planes.items()])


def test_summary_of_a_hand_made_two_chip_trace():
    ar = "%all-reduce.1 = f32[1024,128]{1,0} all-reduce(f32[1024,128] %d)"
    kern = "%vq_window.3 = f32[1024,128]{1,0} custom-call(f32[10,128] %z)"
    profile = _fake_profile({
        "/host:CPU": {"python": [(0, 100, "chunk"), (100, 20, "collect"),
                                 (5, 3, "PjitFunction(body)")]},
        "/device:TPU:0": {"XLA Ops": [(10, 50, "%while.1 = (...) while()"),
                                      (12, 10, kern), (30, 20, ar),
                                      (70, 10, kern)],
                          "XLA Modules": [(10, 70, "jit_body")]},
        "/device:TPU:1": {"XLA Ops": [(10, 40, kern), (55, 30, ar)]},
    })
    s = xplane.summarize(profile)
    assert s.window_s == pytest.approx(120e-9)
    d0, d1 = s.devices
    assert d0.busy_s == pytest.approx(60e-9)       # [10, 60) and [70, 80)
    assert d0.gaps == [(0, 10), (60, 70), (80, 120)]
    assert d0.kernel_s["vq_window"] == pytest.approx(20e-9)
    assert d0.op_self_s["while"] == pytest.approx(20e-9)
    assert d0.collective_s == pytest.approx(20e-9)
    assert d1.busy_s == pytest.approx(70e-9)
    assert s.collective_s() == pytest.approx(25e-9)
    assert s.kernel_s("vq_window") == pytest.approx(30e-9)
    assert s.busy_s == pytest.approx(65e-9)
    assert s.idle_share() == pytest.approx(0.5)
    b = s.breakdown()
    assert b["idle_gaps"][0] == ["collect", pytest.approx(40e-9)]
    assert b["idle_gaps"][1][0] == "chunk"


def _raw(profile):
    """Device ops per chip, as (start_ns, end_ns, name), straight from the
    trace; and the harness spans."""
    devices, spans = {}, []
    for plane in profile.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        for line in plane.lines:
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for e in line.events]
            if m and line.name == "XLA Ops":
                devices[int(m.group(1))] = evs
            elif plane.name == "/host:CPU":
                spans += [e for e in evs if e[2] in xplane.HARNESS_SPANS]
    return devices, spans


def test_recorded_traces_are_kept():
    assert TRACES, "no recorded trace under bench/tests/data"


@pytest.mark.parametrize("path", TRACES, ids=lambda p: p.name)
def test_busy_kernel_and_collective_time(path):
    profile = xplane.read_profile(path)
    summary = xplane.summarize(profile)
    devices, spans = _raw(profile)
    assert sorted(d.index for d in summary.devices) == sorted(devices)
    everything = spans + [o for ops in devices.values() for o in ops]
    t0 = min(o[0] for o in everything)
    t1 = max(o[1] for o in everything)
    assert summary.window_s == pytest.approx((t1 - t0) * 1e-9)
    for dev in summary.devices:
        ops = devices[dev.index]
        # busy: the union of op intervals, on a 1 ns grid
        grid = np.zeros(int(np.ceil(t1 - t0)) + 2, bool)
        for s, e, _ in ops:
            grid[int(round(s - t0)):int(round(e - t0))] = True
        assert dev.busy_s == pytest.approx(grid.sum() * 1e-9, rel=1e-3)
        gaps = sum(e - s for s, e in dev.gaps) * 1e-9
        assert dev.busy_s + gaps == pytest.approx(summary.window_s)
        # kernel time: every event of that kernel, whole duration
        for kernel in ("vq_window", "vq_delta", "vq_assign"):
            want = sum(e - s for s, e, n in ops
                       if re.match(rf"^%{kernel}(\.\d+)? = ", n)) * 1e-9
            assert dev.kernel_s.get(kernel, 0.0) == pytest.approx(want)
        # collective time: every op whose opcode is a collective
        coll = [(s, e, n) for s, e, n in ops if re.search(
            r" (all-reduce|all-gather|reduce-scatter|collective-permute"
            r"|all-to-all)(-start|-done)?\(", n)]
        nested = xplane.self_times(ops)
        want = sum(st for (s, e, n), st in zip(ops, nested)
                   if (s, e, n) in set(coll)) * 1e-9
        assert dev.collective_s == pytest.approx(want, abs=1e-12)
    assert summary.idle_share() == pytest.approx(
        1 - summary.devices[0].busy_s / summary.window_s)


def test_recorded_traces_cover_the_kernels():
    found = {"vq_window": 0, "vq_delta": 0, "vq_assign": 0}
    for path in TRACES:
        summary = xplane.load(path)
        for k in found:
            found[k] += summary.kernel_s(k) > 0
    assert all(found.values()), found


@pytest.mark.parametrize("path", TRACES, ids=lambda p: p.name)
def test_breakdown_is_short_and_names_gaps_by_span(path):
    b = xplane.load(path).breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    names = set(xplane.HARNESS_SPANS) | {"none"}
    assert all(name in names for name, _ in b["idle_gaps"])
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
