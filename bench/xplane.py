"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

What is read:

- device planes ``/device:TPU:<n>``, line ``XLA Ops``: one event per HLO
  operation that ran, named by its HLO text (``%vq_window.7 = f32[...]
  custom-call(...)``).  Control-flow ops (``while``, ``conditional``)
  enclose the ops of their bodies, so op time is taken as self time.
- host plane ``/host:CPU``: the harness's own ``TraceAnnotation`` spans
  (``chunk``, ``collect``, ``submit``, ``sleep``).  Device and host events
  share one clock.

The harness starts the profiler with nothing queued on the device and
stops it once everything it queued has finished, so the traced window
runs from the first harness span or device op to the last one's end.
(The host and device clocks can disagree by some microseconds, so a
chunk's first op may start just before its host span.)  Busy time is the
union of op intervals in it; an idle gap is a stretch of it with no op
running, named by the harness span the host was in at the gap's middle.
"""

from __future__ import annotations

import bisect
import gzip
import dataclasses
import re
from pathlib import Path

HARNESS_SPANS = ("chunk", "collect", "submit", "sleep")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_SUFFIX = re.compile(r"\.\d+$")
_OPCODE = re.compile(r"\b(" + "|".join(COLLECTIVES) + r")(-start|-done)?\(")


def op_base_name(event_name: str) -> str:
    """``%vq_window.7 = f32[..] custom-call(..)`` -> ``vq_window``."""
    lhs = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", lhs)


def is_collective(event_name: str) -> bool:
    base = op_base_name(event_name)
    return (any(base.startswith(c) for c in COLLECTIVES)
            or _OPCODE.search(event_name) is not None)


def union_length(intervals: list[tuple[float, float]]) -> tuple[
        float, list[tuple[float, float]]]:
    """Total length of the union of ``intervals``, and the union itself."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), [(s, e) for s, e in merged]


def self_times(ops: list[tuple[float, float, str]]) -> list[float]:
    """Self time of each op: its length minus that of the ops nested
    directly inside it (same order as ``ops``, sorted by start)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    out = [ops[i][1] - ops[i][0] for i in range(len(ops))]
    stack: list[int] = []
    for i in order:
        s, e, _ = ops[i]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1]:
            out[stack[-1]] -= e - s
        stack.append(i)
    return out


@dataclasses.dataclass
class Device:
    """One chip's share of the traced window (seconds)."""

    index: int
    busy_s: float
    op_self_s: dict[str, float]          # by op base name
    kernel_s: dict[str, float]           # by op base name, whole duration
    collective_s: float
    gaps: list[tuple[float, float]]      # idle stretches, ns on the clock


@dataclasses.dataclass
class Summary:
    window_s: float
    devices: list[Device]
    spans: list[tuple[float, float, str]]   # harness spans, ns

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the chips traced."""
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    def idle_share(self, device: int = 0) -> float:
        return 1.0 - self.devices[device].busy_s / self.window_s

    def kernel_s(self, name: str) -> float:
        """Seconds in kernel ``name``, averaged over the chips traced."""
        return (sum(d.kernel_s.get(name, 0.0) for d in self.devices)
                / len(self.devices))

    def collective_s(self) -> float:
        return (sum(d.collective_s for d in self.devices)
                / len(self.devices))

    def span_at(self, t_ns: float) -> str:
        """Innermost harness span covering ``t_ns`` ("none" outside)."""
        best, best_len = "none", float("inf")
        i = bisect.bisect_right(self.spans, (t_ns, float("inf"), "~"))
        for s, e, name in self.spans[max(0, i - 64):i]:
            if s <= t_ns <= e and e - s < best_len:
                best, best_len = name, e - s
        return best

    def breakdown(self, top: int = 10) -> dict:
        """Top device ops by self time and the longest idle gaps, device 0."""
        d0 = self.devices[0]
        ops = sorted(d0.op_self_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(d0.gaps, key=lambda g: g[0] - g[1])[:top]
        return {
            "device_ops": [[name, s] for name, s in ops],
            "idle_gaps": [[self.span_at((s + e) / 2), (e - s) * 1e-9]
                          for s, e in gaps],
        }


def _events(line):
    for ev in line.events:
        yield ev.start_ns, ev.start_ns + ev.duration_ns, ev.name


def summarize(profile, spans: tuple[str, ...] = HARNESS_SPANS) -> Summary:
    """Reduce a ``jax.profiler.ProfileData`` to a ``Summary``."""
    host: list[tuple[float, float, str]] = []
    device_ops: dict[int, list[tuple[float, float, str]]] = {}
    for plane in profile.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device_ops[int(m.group(1))] = list(_events(line))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend(e for e in _events(line) if e[2] in spans)
    if not device_ops:
        raise ValueError("the trace holds no TPU device plane")
    host.sort()
    allops = [o for ops in device_ops.values() for o in ops]
    edges = host + allops
    if not edges:
        raise ValueError("the trace holds no device operation")
    w0, w1 = min(e[0] for e in edges), max(e[1] for e in edges)
    if w1 <= w0:
        raise ValueError("the traced window is empty")
    devices = []
    for idx in sorted(device_ops):
        ops = sorted(device_ops[idx])
        busy, merged = union_length([(s, e) for s, e, _ in ops])
        gaps, prev = [], w0
        for s, e in merged:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if prev < w1:
            gaps.append((prev, w1))
        selfs = self_times(ops)
        op_self: dict[str, float] = {}
        kernel: dict[str, float] = {}
        coll = 0.0
        for (s, e, name), st in zip(ops, selfs):
            base = op_base_name(name)
            op_self[base] = op_self.get(base, 0.0) + st * 1e-9
            kernel[base] = kernel.get(base, 0.0) + (e - s) * 1e-9
            if is_collective(name):
                coll += st * 1e-9
        devices.append(Device(idx, busy * 1e-9, op_self, kernel, coll, gaps))
    return Summary((w1 - w0) * 1e-9, devices, host)


def find_xplane(log_dir: str | Path) -> Path:
    """The one ``.xplane.pb`` a ``jax.profiler`` session wrote."""
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise ValueError(f"want one .xplane.pb under {log_dir}, "
                         f"found {len(found)}")
    return found[0]


def load(path: str | Path) -> Summary:
    """Summary of the trace at ``path`` (``.xplane.pb``, or gzipped)."""
    return summarize(read_profile(path))


def read_profile(path: str | Path):
    from jax.profiler import ProfileData

    path = Path(path)
    if path.suffix == ".gz":
        return ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes()))
    return ProfileData.from_file(str(path))
