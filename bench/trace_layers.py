"""Read where a cell's time goes inside the program, in one process.

    python bench/trace_layers.py --workload <cell> --seed <n> [--seconds 10]

Runs the cell's set-up as ``run.py`` does, then:

- a training cell: its traced run (``trace_chunks`` chunks under the
  profiler, then the window), reading from the trace the chip's self time
  under each of the engine's named scopes (``eval_probe``,
  ``local_window``, ``merge``) as a share of the traced window;
- a serving cell: its traced run, keeping every response.  From the
  untraced window: each request's queue wait (``queued_s``, submit to
  taken into a flush) and its time in the service after that.  From the
  trace: the durations of ``serve.flush`` and of its children, and the
  chip's idle time split by the flush thread's span it falls in.  Then
  one window with the profiler on from its start to its end, and one with
  a recording ``Tracer``: what the instrumentation costs.

In every cell it times ``Tracer.span`` on a disabled tracer, with no
profiler session open.  Prints one JSON line.  Needs the chips the cell
asks for, like ``run.py``.  On a program without these spans or scopes,
the readings that need them are null.
"""

import argparse
import contextlib
import gc
import json
import math
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
SCOPES = ("eval_probe", "local_window", "merge")
FLUSH_CHILDREN = ("serve.gather", "serve.lookup", "serve.sync",
                  "serve.respond")
FLUSH_THREAD = ("serve.idle_wait", "serve.batch_wait", "serve.flush")


def p50_ms(secs) -> float | None:
    import numpy as np

    return float(np.median(secs)) * 1e3 if len(secs) else None


def span_cost_us(n: int = 100_000) -> dict:
    """Microseconds per ``with`` block: a disabled tracer's span, a
    recording tracer's span, and an empty generator context manager (the
    whole of a disabled span before spans reached the profiler)."""
    from repro.obs import NULL_TRACER, Tracer

    @contextlib.contextmanager
    def empty():
        yield None

    recording = Tracer(max_spans=n)
    out = {}
    for name, make in (("disabled_span_us", lambda: NULL_TRACER.span("x")),
                       ("recording_span_us", lambda: recording.span("x")),
                       ("empty_contextmanager_us", empty)):
        t = time.perf_counter()
        for _ in range(n):
            with make():
                pass
        out[name] = (time.perf_counter() - t) / n * 1e6
    return out


def read_trace(profile):
    """(summary, profile data, serialized XSpace) of a finished session."""
    from jax.profiler import ProfileData

    import xplane
    import xplane_scopes

    profile.end()
    raw = xplane_scopes.read_bytes(xplane.find_xplane(profile.dir))
    data = ProfileData.from_serialized_xspace(raw)
    return xplane.summarize(data), data, raw


def durations(spans, name) -> list[float]:
    return [(e - s) * 1e-9 for s, e, n in spans if n == name]


def train(driver, seconds: float) -> dict:
    import harness
    import xplane_scopes

    profile = harness.Profile()
    try:
        e2e, counters = driver.window(seconds, profile)
        summary, data, raw = read_trace(profile)
    finally:
        profile.close()
    scopes = xplane_scopes.scope_self_s(data, xplane_scopes.op_paths(raw))
    spans = xplane_scopes.program_spans(data)
    return {
        **e2e, "window_s": summary.window_s, "busy_s": summary.busy_s,
        "scope_share": {s: (100.0 * scopes[s] / summary.window_s
                            if s in scopes else None) for s in SCOPES},
        "program_spans": {n: len(durations(spans, n))
                          for n in sorted({n for _, _, n in spans})},
        "breakdown": summary.breakdown(),
        "chunk_gap_ms": counters.get("chunk_gap_ms")}


def _responses(futures) -> list:
    return [f.result() for f in futures if f.done() and not f.exception()]


def _service_split(responses) -> dict:
    queued = [r.queued_s for r in responses if hasattr(r, "queued_s")]
    after = [r.latency_s - r.queued_s for r in responses
             if hasattr(r, "queued_s")]
    return {"queue_wait_p50_ms": p50_ms(queued),
            "after_queue_p50_ms": p50_ms(after),
            "service_latency_p50_ms": p50_ms([r.latency_s
                                              for r in responses])}


def _flush_thread(summary, spans) -> dict:
    """The flush spans' durations, and the chip's idle time on device 0
    split by the flush thread's span it falls in."""
    import xplane_scopes

    gaps = summary.devices[0].gaps
    idle = sum(e - s for s, e in gaps) * 1e-9

    def idle_in(name):
        return xplane_scopes.overlap_s(
            gaps, [(s, e) for s, e, n in spans if n == name])

    split = {n: idle_in(n) for n in FLUSH_THREAD + FLUSH_CHILDREN}
    split["serve.flush (own)"] = split["serve.flush"] - sum(
        split[n] for n in FLUSH_CHILDREN)
    split["none"] = idle - sum(split[n] for n in FLUSH_THREAD)
    flush = durations(spans, "serve.flush")
    return {
        "flush_p50_ms": p50_ms(flush), "flushes": len(flush),
        "child_p50_ms": {n: p50_ms(durations(spans, n))
                         for n in FLUSH_CHILDREN},
        "child_share_of_flush": {
            n: (sum(durations(spans, n)) / sum(flush) if flush else None)
            for n in FLUSH_CHILDREN},
        "idle_s": idle, "window_s": summary.window_s,
        "idle_split_s": split}


def serve(driver, seconds: float) -> dict:
    import harness
    import xplane_scopes
    from repro.obs import Tracer

    svc, futures = driver.svc, []
    submit = svc.submit

    def keep(z):
        fut = submit(z)
        futures.append(fut)
        return fut

    svc.submit = keep
    out = {}
    # 1. the traced run: trace_seconds under the profiler, then the window
    profile = harness.Profile()
    try:
        e2e, counters = driver.window(seconds, profile)
        summary, data, _ = read_trace(profile)
    finally:
        profile.close()
    traced = math.ceil(driver.tr["rate_rps"] * driver.tr["trace_seconds"])
    out["untraced"] = {**e2e, "lag_p99_ms": counters["lag_p99_ms"],
                       "rows_per_flush": counters["rows_per_flush"],
                       **_service_split(_responses(futures[traced:]))}
    out["traced"] = _flush_thread(summary, xplane_scopes.program_spans(data))
    # 2. the profiler on over a whole window
    futures.clear()
    svc.start()
    profile = harness.Profile()
    try:
        profile.begin()
        e2e, _ = driver.window(seconds, None)
        _, data, _ = read_trace(profile)
    finally:
        profile.close()
    flush = durations(xplane_scopes.program_spans(data), "serve.flush")
    out["profiler_on"] = {**e2e, "flush_p50_ms": p50_ms(flush),
                          **_service_split(_responses(futures))}
    # 3. the profiler off, a recording tracer timing the flushes
    futures.clear()
    svc.tracer = Tracer()
    svc.start()
    e2e, _ = driver.window(seconds, None)
    flush = [s.dur_us * 1e-6 for s in svc.tracer.spans("serve.flush")]
    out["tracer_recording"] = {**e2e, "flush_p50_ms": p50_ms(flush),
                               **_service_split(_responses(futures))}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(BENCH.parent / "src"))
    import harness

    try:
        cell = harness.find_cell(args.workload)
        devices = harness.prepare(cell)
    except harness.HarnessError as e:
        print(f"trace_layers: {e}", file=sys.stderr)
        return 1
    driver = harness.make_driver(cell, args.seed, devices)
    driver.setup()
    gc.collect()
    gc.freeze()  # as a run does: see harness.run_cell
    result = {"workload": args.workload, "seed": args.seed,
              "device": devices[0].device_kind,
              "span_cost": span_cost_us()}
    if cell.kind == "train_stream":
        result.update(train(driver, args.seconds))
    else:
        result.update(serve(driver, args.seconds))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
