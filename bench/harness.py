"""Run one benchmark cell once and build its result line.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``      sizes, scheme and precision;
- ``traffic/<traffic>.json``     a ``kind`` and its parameters;
- ``drivers/<kind>.py``          one driver per traffic kind;
- ``limits/<cell>.json``         the limit of each number ``correct``
                                 compares, per cell;
- ``metrics/<metric>.py``        one reader per per-layer metric;
- ``work/<layer>.py``            operation and byte counts.

A driver has ``setup()``, ``window(seconds, profile)`` returning its
end-to-end numbers and its counters, ``check()`` returning the numbers it
compares with the plain reference, and the counts ``attempted`` and
``failed``.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType, SimpleNamespace

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class HarnessError(Exception):
    """A run that cannot produce a result (no chip, a missing file)."""


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise HarnessError(f"missing file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise HarnessError(f"missing file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of the manifest with everything it names."""

    name: str
    entry: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def find_cell(name: str, manifest: dict | None = None) -> Cell:
    """Resolve a workload name to its config, traffic, limits and metrics."""
    manifest = manifest or load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise HarnessError(f"no workload {name!r}; known: {sorted(cells)}")
    entry = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(ROOT / configs[entry["config"]]["file"])
    traffic = load_json(BENCH / "traffic" / f"{entry['traffic']}.json")
    limits_path = BENCH / "limits" / f"{name}.json"
    limits = load_json(limits_path) if limits_path.is_file() else {}
    e2e = [m for m in manifest["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in moved)]
    return Cell(name, entry, config, traffic, limits, e2e, layer)


def load_driver(kind: str) -> ModuleType:
    return load_module(BENCH / "drivers" / f"{kind}.py", f"bench_driver_{kind}")


def check_devices(chips: int) -> list:
    """The chips the cell asks for; a HarnessError where JAX finds no TPU,
    where Pallas would run in interpret mode, or too few chips."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise HarnessError(f"JAX found no devices: {e}") from e
    if devices[0].platform != "tpu":
        raise HarnessError(f"no TPU: JAX's first device is "
                           f"{devices[0].platform!r}")
    from repro.kernels import ops

    if ops._interpret_default():
        raise HarnessError("the Pallas kernels would run in interpret mode")
    if len(devices) < chips:
        raise HarnessError(f"the cell needs {chips} TPU chips, JAX found "
                           f"{len(devices)}")
    return devices[:chips]


class Profile:
    """A ``jax.profiler`` session the driver opens around what it wants
    traced; the trace goes to a temporary directory, removed after use."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.state = "idle"

    def begin(self) -> None:
        import jax

        if self.state != "idle":
            return
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.state = "open"

    def end(self) -> None:
        import jax

        if self.state == "open":
            jax.profiler.stop_trace()
            self.state = "done"

    def summary(self):
        import xplane

        if self.state == "open":
            self.end()
        if self.state != "done":
            raise HarnessError("the driver traced nothing")
        return xplane.load(xplane.find_xplane(self.dir))

    def close(self) -> None:
        self.end()
        shutil.rmtree(self.dir, ignore_errors=True)


class GcWatch:
    """Python's garbage collections while it watches: how many of each
    generation, and the longest and total pause.  A collection holds the
    interpreter lock, so its start and stop never interleave with
    another's."""

    def __init__(self):
        self.pauses: list[tuple[int, float]] = []
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))
            self._t0 = None

    def close(self) -> dict:
        gc.callbacks.remove(self._on)
        secs = [s for _, s in self.pauses]
        return {"gc_collections": [sum(g == i for g, _ in self.pauses)
                                   for i in range(3)],
                "gc_max_ms": max(secs, default=0.0) * 1e3,
                "gc_total_ms": sum(secs) * 1e3}


class CompileCounter:
    """Programs compiled, from JAX's monitoring events.  JAX reports a
    backend compile also for a program it finds in the persistent cache,
    so cache hits are taken off."""

    def __init__(self):
        import jax

        self.backend = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    @property
    def count(self) -> int:
        return self.backend - self.cache_hits

    def _on_duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.backend += 1

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1


def judge(checks: dict[str, float], limits: dict) -> tuple[bool, dict]:
    """``correct`` and each compared number beside its limit.

    The cell's limits file names the numbers compared: each has to be
    there, finite and within its limit.  A number the driver reads that
    has no limit is shown with the limit ``None`` and not compared (the
    readings found no limit that separates sound runs from the control)."""
    out, ok = {}, bool(limits)
    for name, value in checks.items():
        out[name] = {"value": value, "limit": limits.get(name)}
    for name, limit in limits.items():
        value = checks.get(name, math.nan)
        ok &= math.isfinite(value) and value <= limit
    return ok, out


def prepare(cell: Cell, require_tpu: bool = True) -> list:
    """The cell's devices, with the compile cache on and ``bench/`` on the
    import path."""
    import jax

    chips = cell.entry["chips"]
    devices = (check_devices(chips) if require_tpu
               else jax.devices()[:chips])
    from repro import compile_cache

    compile_cache.enable()
    # every program, however quick to compile, comes from the cache after
    # a cell's first run, so set-up repeats the same work
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    return devices


def make_driver(cell: Cell, seed: int, devices: list,
                variant: str | None = None):
    """The cell's driver; ``variant`` puts the control or a planted fault
    in the program's place (for reading limits, never in a timed run)."""
    ctx = SimpleNamespace(cell=cell, config=cell.config,
                          traffic=cell.traffic, seed=seed, devices=devices,
                          variant=variant, log=_log)
    return load_driver(cell.kind).Driver(ctx)


def read_layer_metrics(cell: Cell, run: SimpleNamespace) -> dict:
    out = {}
    for m in cell.per_layer:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                             f"bench_metric_{len(out)}")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(name: str, *, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             cell: Cell | None = None) -> dict:
    """One run of one cell: set-up, the measured window, the check."""
    cell = cell or find_cell(name)
    devices = prepare(cell, require_tpu)
    chips = len(devices)
    compiles = CompileCounter()
    driver = make_driver(cell, seed, devices)
    profile = Profile() if trace else None
    try:
        driver.setup()
        # What set-up leaves on the heap (JAX's modules and caches, the
        # data, the client's pool) is set apart from the collector, which
        # would otherwise walk all of it in the window: a full collection
        # there pauses every thread for some 0.1 s, in some runs only.
        gc.collect()
        gc.freeze()
        setup_s = time.perf_counter() - t_start
        before = compiles.count
        watch = GcWatch()
        try:
            e2e, counters = driver.window(seconds, profile)
        finally:
            counters_gc = watch.close()
        counters.update(counters_gc)
        counters["compiles_in_setup"] = before
        counters["cache_hits_in_setup"] = compiles.cache_hits
        counters["compiles_in_window"] = compiles.count - before
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        summary = profile.summary() if trace else None
        checks = driver.check()
    finally:
        gc.unfreeze()
        if profile is not None:
            profile.close()
    correct, compared = judge(checks, cell.limits)
    dev = devices[0]
    result = {"correct": correct, "attempted": driver.attempted,
              "failed": driver.failed}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        import peaks

        run = SimpleNamespace(summary=summary, counters=counters,
                              config=cell.config, traffic=cell.traffic,
                              chips=chips, peaks=peaks.peaks(dev.device_kind),
                              load_work=lambda layer: load_module(
                                  BENCH / "work" / f"{layer}.py",
                                  f"bench_work_{layer}"))
        result["metrics"] = read_layer_metrics(cell, run)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["device"] = device
        result["breakdown"] = summary.breakdown()
    else:
        metrics = {}
        for m in cell.end_to_end:
            value = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
    _log("counters " + json.dumps(counters, default=float))
    result["checks"] = compared
    return result


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def print_result(result: dict) -> None:
    for name, c in result["checks"].items():
        _log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    _log(f"correct = {result['correct']}")
    print(json.dumps(result), flush=True)
