"""What ``xplane.py`` leaves out of a profiler trace: the JAX name stack of
each device op, and the program's own host spans.

- Each device plane's ``event_metadata`` holds one entry per HLO op that
  ran, whose ``tf_op`` stat is JAX's name stack for it, e.g.
  ``jit(body)/while/body/closed_call/vq_window/pallas_call:``.  A
  ``jax.named_scope`` in the program is one component of that path, so
  the device time of a scope is found by name, whatever XLA calls its
  fusions.  ``jax.profiler.ProfileData`` shows event stats only, so the
  metadata is read here from the serialized ``XSpace`` with the standard
  library (the message and field numbers of ``tsl/profiler/protobuf/
  xplane.proto``).
- The program names its wall spans ``<layer>.<what>`` (``serve.flush``,
  ``engine.segment``); they sit on the host plane beside the harness's
  own spans, on the same clock as the device's ops.  They are kept apart
  from ``xplane.HARNESS_SPANS``: the traced window's edges and the idle
  gaps' names come from the harness's spans alone.
"""

from __future__ import annotations

import gzip
import re
from pathlib import Path

import xplane

PROGRAM_LAYERS = ("serve.", "engine.", "loadgen.", "elastic.")
_OP_TYPE = re.compile(r":[^/]*$")

# xplane.proto: XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
# .stat_metadata = 5 (maps: key = 1, value = 2); XEventMetadata.name = 2,
# .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1,
# .str_value = 5, .ref_value = 7.
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 4, 5
_META_NAME, _META_STATS = 2, 5
_STAT_META_ID, _STAT_STR, _STAT_REF = 1, 5, 7


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, start: int, end: int):
    """(field number, value) of the message in ``buf[start:end]``: an int
    for a varint, a ``(start, end)`` span of ``buf`` for a length-delimited
    field, raw bytes for a fixed one."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield num, value


def _text(buf: bytes, span: tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entries(buf: bytes, span: tuple[int, int]):
    """(key, value span) of one ``map<int64, message>`` entry."""
    key, value = 0, (span[0], span[0])
    for num, v in _fields(buf, *span):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _plane_op_paths(buf: bytes, span: tuple[int, int]
                    ) -> tuple[str, dict[str, str]]:
    name, events, stat_names = "", [], {}
    for num, v in _fields(buf, *span):
        if num == _PLANE_NAME:
            name = _text(buf, v)
        elif num == _PLANE_EVENT_META:
            events.append(_map_entries(buf, v)[1])
        elif num == _PLANE_STAT_META:
            key, meta = _map_entries(buf, v)
            for n, mv in _fields(buf, *meta):
                if n == _META_NAME:
                    stat_names[key] = _text(buf, mv)
    tf_op = {k for k, v in stat_names.items() if v == "tf_op"}
    paths: dict[str, str] = {}
    if not tf_op:
        return name, paths
    for meta in events:
        op, path = None, None
        for num, v in _fields(buf, *meta):
            if num == _META_NAME:
                op = _text(buf, v)
            elif num == _META_STATS:
                stat = dict(_fields(buf, *v))
                if stat.get(_STAT_META_ID) not in tf_op:
                    continue
                if _STAT_STR in stat:
                    path = _text(buf, stat[_STAT_STR])
                elif _STAT_REF in stat:
                    path = stat_names.get(stat[_STAT_REF])
        if op is not None and path:
            paths[op] = _OP_TYPE.sub("", path)
    return name, paths


def op_paths(xspace: bytes) -> dict[str, dict[str, str]]:
    """``{device plane name: {op event name: name stack}}`` of a
    serialized ``XSpace``; the name stack drops the ``:<type>`` tail that
    the ``tf_op`` convention adds."""
    out = {}
    for num, v in _fields(xspace, 0, len(xspace)):
        if num == _SPACE_PLANES:
            name, paths = _plane_op_paths(xspace, v)
            if xplane._DEVICE_PLANE.match(name):
                out[name] = paths
    return out


def read_bytes(path: str | Path) -> bytes:
    """The serialized ``XSpace`` at ``path`` (``.xplane.pb``, or gzipped)."""
    raw = Path(path).read_bytes()
    return gzip.decompress(raw) if str(path).endswith(".gz") else raw


def _device_ops(profile, device: int = 0) -> list[tuple[float, float, str]]:
    """The ``XLA Ops`` events of chip ``device``, sorted."""
    for plane in profile.planes:
        m = xplane._DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) == device:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    return sorted(xplane._events(line))
    return []


def scope_self_s(profile, paths: dict[str, dict[str, str]],
                 device: int = 0) -> dict[str, float]:
    """Seconds of device self time on chip ``device`` under each component
    of the ops' name stacks (an op counts once per scope it is under)."""
    ops = _device_ops(profile, device)
    names = paths.get(f"/device:TPU:{device}", {})
    out: dict[str, float] = {}
    for (_, _, name), st in zip(ops, xplane.self_times(ops)):
        for scope in set(names.get(name, "").split("/")) - {""}:
            out[scope] = out.get(scope, 0.0) + st * 1e-9
    return out


def program_spans(profile, layers: tuple[str, ...] = PROGRAM_LAYERS
                  ) -> list[tuple[float, float, str]]:
    """The program's own host spans (named ``<layer>.<what>``), sorted."""
    out = []
    for plane in profile.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out.extend(e for e in xplane._events(line)
                           if e[2].startswith(layers))
    return sorted(out)


def overlap_s(a: list[tuple[float, float]],
              b: list[tuple[float, float]]) -> float:
    """Seconds in which an interval of ``a`` and one of ``b`` (each a
    list of disjoint ns intervals) both run."""
    a, b = sorted(a), sorted(b)
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total * 1e-9
