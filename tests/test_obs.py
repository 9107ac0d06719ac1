"""Observability layer tests (ISSUE 6).

``repro.obs`` is host-side plumbing — tracer, metrics registry, trace
checker — so most tests are pure-Python unit tests; the integration
tests pin the two contracts the rest of the repo relies on:

* an observed mesh run produces the SAME distortion curve as a bare run
  (instrumentation must not perturb numerics), and its exported trace
  passes every ``check_trace`` invariant;
* hierarchical comm accounting stays single-counted when mirrored into
  metrics (the ``_delegate`` re-tag-exactly-once guard).
"""

from repro.xla_flags import force_host_devices

force_host_devices(8)

import concurrent.futures  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from repro import comm  # noqa: E402
from repro.comm.api import CommRecord  # noqa: E402
from repro.comm.hier import HierarchicalTransport  # noqa: E402
from repro.comm.xla import XlaTransport  # noqa: E402
from repro.data import synthetic  # noqa: E402
from repro.engine import InstantNetwork, MeshExecutor  # noqa: E402
from repro.obs import (NULL_TRACER, MetricsRegistry, Tracer,  # noqa: E402
                       check_trace, format_metric, load_jsonl, load_trace)
from repro.obs import check as obs_check  # noqa: E402
from repro.serve.loadgen import run_load  # noqa: E402
from repro.topology import Topology  # noqa: E402

KEY = jax.random.PRNGKey(42)


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

def test_wall_spans_nest_and_time_monotonically():
    tr = Tracer()
    with tr.span("outer", kind="test"):
        with tr.span("inner"):
            time.sleep(0.002)
        assert tr.open_spans == 1
    assert tr.open_spans == 0
    outer, = tr.spans("outer")
    inner, = tr.spans("inner")
    assert outer.attrs == {"kind": "test"}
    assert inner.start_us >= outer.start_us
    assert inner.dur_us >= 2_000 * 0.5          # slept 2ms (timer slack)
    assert outer.dur_us >= inner.dur_us
    assert inner.start_us + inner.dur_us <= outer.start_us + outer.dur_us + 1


def test_modeled_spans_and_counters():
    tr = Tracer()
    tr.add_span("compute", 0.0, 10.0, track="worker 0", window=0)
    tr.add_span("merge", 10.0, -3.0, track="worker 0")   # clamped to 0
    tr.counter("distortion", 1.5, ts_us=10.0)
    assert tr.spans("merge")[0].dur_us == 0.0
    assert tr.spans("compute")[0].process == Tracer.TICK_PROCESS
    c, = tr.counters("distortion")
    assert (c.value, c.ts_us) == (1.5, 10.0)


def test_null_tracer_is_inert():
    with NULL_TRACER.span("x") as ev:
        assert ev is None
    NULL_TRACER.add_span("y", 0.0, 1.0, track="t")
    NULL_TRACER.counter("z", 1.0)
    assert NULL_TRACER.spans() == [] and NULL_TRACER.counters() == []


def test_wall_spans_open_a_profiler_annotation_recording_or_not(monkeypatch):
    """Every wall span opens a ``jax.profiler.TraceAnnotation`` of its
    name; a disabled tracer still opens it and records nothing, and
    modeled spans never reach the profiler."""
    opened = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(("enter", self.name))

        def __exit__(self, *exc):
            opened.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    off = Tracer(enabled=False)
    with off.span("serve.flush", rows=3) as ev:
        with off.span("serve.lookup"):
            pass
    assert ev is None
    with pytest.raises(ValueError), off.span("serve.sync"):
        raise ValueError("closed on the way out")
    assert opened == [("enter", "serve.flush"), ("enter", "serve.lookup"),
                      ("exit", "serve.lookup"), ("exit", "serve.flush"),
                      ("enter", "serve.sync"), ("exit", "serve.sync")]
    assert off.spans() == [] and off.open_spans == 0

    opened.clear()
    on = Tracer()
    with on.span("engine.segment"):
        assert on.open_spans == 1
    on.add_span("compute", 0.0, 1.0, track="worker 0")
    assert opened == [("enter", "engine.segment"), ("exit", "engine.segment")]
    assert [e.name for e in on.spans()] == ["engine.segment", "compute"]


def test_wall_spans_use_thread_name_as_track():
    tr = Tracer()

    def work():
        with tr.span("threaded"):
            pass

    t = threading.Thread(target=work, name="worker-7")
    t.start()
    t.join()
    assert tr.spans("threaded")[0].track == "worker-7"


def test_chrome_export_roundtrip_names_every_lane(tmp_path):
    tr = Tracer()
    with tr.span("run"):
        pass
    tr.add_span("window", 0.0, 5.0, track="worker 0")
    tr.add_span("merge", 2.0, 3.0, track="merge flat", tier="flat",
                wire_bytes=64)
    tr.counter("distortion", 2.0, ts_us=5.0)
    path = tmp_path / "out.trace.json"
    tr.export_chrome(str(path))

    events = load_trace(str(path))
    assert check_trace(events, expect_merge_tiers={"flat"},
                       expect_counters=["distortion"]) == []
    # every pid/tid any X event references is named by M metadata
    phs = {e["ph"] for e in events}
    assert phs == {"M", "X", "C"}
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"


def test_unclosed_span_is_marked_and_flagged():
    tr = Tracer()
    cm = tr.span("dangling")
    cm.__enter__()                       # never exited
    events = tr.chrome_events()
    (x,) = [e for e in events if e["ph"] == "X"]
    assert x["args"]["unclosed"] is True
    errs = check_trace(events)
    assert any("never closed" in e for e in errs)


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------

def test_counter_and_gauge_semantics():
    reg = MetricsRegistry()
    reg.counter("wire", tier=0).inc(10)
    reg.counter("wire", tier=0).inc(5)          # same instrument
    reg.counter("wire", tier=1).inc(1)          # distinct by label
    assert reg.counter("wire", tier=0).value == 15
    g = reg.gauge("depth")
    for v in (3.0, 1.0, 2.0):
        g.set(v)
    snap = g.snapshot()
    assert (snap["value"], snap["min"], snap["max"], snap["n"]) == \
        (2.0, 1.0, 3.0, 3)


def test_histogram_quantiles_track_numpy_within_bucket_error():
    rng = np.random.default_rng(0)
    samples = np.exp(rng.normal(0.0, 1.0, size=4000))
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    for v in samples:
        h.observe(float(v))
    for q in (0.50, 0.99):
        approx = h.quantile(q)
        exact = float(np.quantile(samples, q))
        # geometric buckets with ratio 2**(1/8) bound relative error ~4.5%
        assert abs(approx - exact) / exact < 0.06, (q, approx, exact)
    assert h.count == len(samples)
    np.testing.assert_allclose(h.mean, samples.mean(), rtol=1e-6)


def test_histogram_edge_cases():
    h = MetricsRegistry().histogram("x")
    assert h.quantile(0.5) == 0.0                # empty
    h.observe(7.0)
    assert h.quantile(0.0) == h.quantile(1.0) == 7.0   # single sample clamps
    h2 = MetricsRegistry().histogram("y")
    h2.observe(0.0)
    h2.observe(-1.0)                             # non-positive -> zero bucket
    assert h2.quantile(0.5) == 0.0               # zero-bucket representative
    assert (h2.min, h2.max) == (-1.0, 0.0)       # range stays exact
    with pytest.raises(ValueError):
        h2.quantile(1.5)


def test_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("m", a=1)
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("m", a=1)
    reg.gauge("m", a=2)                          # other labels are fine


def test_format_metric_and_summary_table():
    assert format_metric("wire", {}) == "wire"
    assert format_metric("wire", {"tier": 1, "tag": "merge"}) == \
        "wire{tag=merge, tier=1}"
    reg = MetricsRegistry()
    reg.counter("c").inc(2)
    reg.gauge("g").set(0.5)
    reg.histogram("h").observe(1.0)
    table = reg.summary_table()
    for needle in ("metric", "c", "g", "h", "p50", "p99"):
        assert needle in table


def test_jsonl_sink_appends_and_roundtrips(tmp_path):
    path = tmp_path / "metrics.jsonl"
    reg = MetricsRegistry()
    reg.counter("n").inc(1)
    assert reg.dump_jsonl(str(path), run="a") == 1
    reg.counter("n").inc(1)
    assert reg.dump_jsonl(str(path), run="b") == 1
    rows = load_jsonl(str(path))
    assert [(r["run"], r["value"]) for r in rows] == [("a", 1.0), ("b", 2.0)]
    reg.dump_jsonl(str(path), append=False)      # truncate mode
    assert len(load_jsonl(str(path))) == 1


# ---------------------------------------------------------------------------
# check_trace invariants
# ---------------------------------------------------------------------------

def _meta(pid, tid=None):
    if tid is None:
        return {"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
                "args": {"name": f"p{pid}"}}
    return {"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
            "args": {"name": f"t{tid}"}}


def _x(name, ts, dur, pid=1, tid=1, **args):
    return {"ph": "X", "name": name, "ts": ts, "dur": dur, "pid": pid,
            "tid": tid, "args": args}


def test_check_trace_accepts_clean_nesting():
    events = [_meta(1), _meta(1, 1),
              _x("outer", 0.0, 10.0),
              _x("inner", 2.0, 3.0),
              _x("later", 6.0, 4.0)]            # shares outer's end: nested
    assert check_trace(events) == []


def test_check_trace_flags_each_violation():
    # merge without tier / with bad wire_bytes
    errs = check_trace([_meta(1), _meta(1, 1),
                        _x("merge", 0.0, 1.0, wire_bytes=8),
                        _x("merge", 2.0, 1.0, tier=0, wire_bytes=-4)])
    assert any("missing 'tier'" in e for e in errs)
    assert any("wire_bytes" in e for e in errs)
    # same-track straddle
    errs = check_trace([_meta(1), _meta(1, 1),
                        _x("a", 0.0, 5.0), _x("b", 3.0, 5.0)])
    assert any("straddles" in e for e in errs)
    # unnamed pid/tid
    errs = check_trace([_x("a", 0.0, 1.0, pid=9, tid=9)])
    assert any("no process_name" in e for e in errs)
    assert any("no thread_name" in e for e in errs)
    # begin/end pairs are banned (exporter emits complete spans only)
    errs = check_trace([{"ph": "B", "name": "a", "ts": 0, "pid": 1, "tid": 1}])
    assert any("begin/end" in e for e in errs)
    # negative duration
    errs = check_trace([_meta(1), _meta(1, 1), _x("a", 0.0, -1.0)])
    assert any("bad dur" in e for e in errs)
    # counter without a numeric timestamp
    errs = check_trace([{"ph": "C", "name": "c", "pid": 1, "tid": 0,
                         "args": {"c": 1.0}}])
    assert any("no numeric ts" in e for e in errs)


def test_check_trace_expectations():
    events = [_meta(1), _meta(1, 1),
              _x("merge", 0.0, 1.0, tier=0, wire_bytes=8),
              {"ph": "C", "name": "distortion", "ts": 1.0, "pid": 1,
               "tid": 0, "args": {"distortion": 2.0}}]
    assert check_trace(events, expect_merge_tiers={"0"},
                       expect_counters=["distortion"]) == []
    errs = check_trace(events, expect_merge_tiers={"0", "1"},
                       expect_counters=["codebook_divergence"])
    assert any("expected merge tiers ['1']" in e for e in errs)
    assert any("codebook_divergence" in e for e in errs)


def test_check_cli_exit_codes(tmp_path, capsys):
    tr = Tracer()
    tr.add_span("merge", 0.0, 1.0, track="t", tier="flat", wire_bytes=0)
    good = tmp_path / "good.json"
    tr.export_chrome(str(good))
    assert obs_check.main([str(good), "--expect-merge-tiers", "flat"]) == 0
    assert "OK" in capsys.readouterr().out

    assert obs_check.main([str(good), "--expect-merge-tiers", "0,1",
                           "--expect-counter", "distortion"]) == 1
    out = capsys.readouterr().out
    assert "FAIL" in out and "distortion" in out

    bad = tmp_path / "bad.json"
    bad.write_text("{}")
    assert obs_check.main([str(bad)]) == 1


# ---------------------------------------------------------------------------
# engine integration: observing must not perturb numerics
# ---------------------------------------------------------------------------

def _setup(m, n=400, d=8, kappa=16):
    kd, kw = jax.random.split(KEY)
    data = synthetic.replicate_stream(kd, m, n=n, d=d)
    eval_data = data[:, :200]
    w0 = synthetic.kmeanspp_init(kw, data.reshape(-1, d), kappa)
    return data, eval_data, w0


@pytest.mark.devices(4)
@pytest.mark.parametrize("scheme", ["delta", "async_delta"])
def test_observed_mesh_run_matches_bare_and_trace_is_clean(scheme):
    m = 4
    data, eval_data, w0 = _setup(m)
    kw = {"tau": 10, "key": jax.random.fold_in(KEY, 1)}
    bare = MeshExecutor(network=InstantNetwork()).run(
        scheme, w0, data, eval_data, **kw)
    tr, reg = Tracer(), MetricsRegistry()
    obs = MeshExecutor(network=InstantNetwork(), tracer=tr,
                       metrics=reg).run(scheme, w0, data, eval_data, **kw)

    np.testing.assert_allclose(np.asarray(obs.distortion),
                               np.asarray(bare.distortion),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(obs.w_shared),
                               np.asarray(bare.w_shared),
                               rtol=1e-5, atol=1e-7)

    # async merges are masked per-tick sums; divergence-vs-consensus only
    # exists on the windowed sync timeline
    expect = (["distortion"] if scheme == "async_delta"
              else ["distortion", "codebook_divergence"])
    errs = check_trace(tr.chrome_events(), expect_merge_tiers={"flat"},
                       expect_counters=expect)
    assert errs == []
    if scheme == "async_delta":
        assert reg.counter("async_rounds_total", scheme=scheme).value > 0
    else:
        assert reg.counter("windows_total", scheme=scheme).value > 0
        assert reg.gauge("codebook_divergence", scheme=scheme).n > 0
        # per-worker modeled tracks exist for every worker
        tracks = {s.track for s in tr.spans("window")}
        assert tracks == {f"worker {w}" for w in range(m)}


@pytest.mark.devices(8)
def test_hier_metrics_mirror_is_single_counted():
    """Satellite: CommLog metrics attach at the top level only, so the
    mirrored wire-byte counters equal the log summary (no double count
    from the sub-transports' own logs)."""
    m = 8
    data, eval_data, w0 = _setup(m)
    topo = Topology.from_spec(m, hosts=2)
    reg = MetricsRegistry()
    ex = MeshExecutor(
        topology=topo,
        transport=comm.HierarchicalTransport(
            tier0="xla", tier1="xla", host_axis=topo.host_axis,
            worker_axis=topo.worker_axis),
        network=InstantNetwork(), metrics=reg)
    ex.run("delta", w0, data, eval_data, tau=10)

    merge = ex.last_comm["by_tag"]["merge"]
    by_tier = merge["by_tier"]
    assert set(by_tier) == {0, 1}
    # summary total == sum of its tiers (the accounting identity)
    assert merge["wire_bytes"] == sum(t["wire_bytes"]
                                      for t in by_tier.values())
    # and the metrics mirror saw exactly the same per-tier totals
    for tier, t in by_tier.items():
        c = reg.counter("comm_wire_bytes", tag="merge", tier=tier,
                        transport="xla")
        assert c.value == t["wire_bytes"]


# ---------------------------------------------------------------------------
# satellite: hier re-tag-exactly-once guards
# ---------------------------------------------------------------------------

class _PokingTransport(XlaTransport):
    """Test double of a sub-transport whose call logs one record."""

    def poke(self, rec_tier=None):
        self.log.append(CommRecord(
            op="sum", transport=self.name, axis="workers", participants=2,
            logical_bytes=8, wire_bytes=8, tier=rec_tier))
        return 42


def test_hier_rejects_nested_hier_tiers():
    # hier-over-sparse: the default composition (dense tier 0, sparse
    # top-k tier 1) must not itself become a tier of an outer hier
    inner = HierarchicalTransport()
    with pytest.raises(ValueError, match="tier0=.*nest"):
        HierarchicalTransport(tier0=inner, tier1="xla")
    with pytest.raises(ValueError, match="tier1=.*nest"):
        HierarchicalTransport(tier0="xla", tier1=inner)


def test_delegate_retags_exactly_once():
    sub = _PokingTransport()
    hier = HierarchicalTransport(tier0=sub, tier1="xla")
    assert hier._delegate(sub, 1, "poke") == 42
    # outer log got the tier-tagged copy; the sub's record is untouched
    assert [r.tier for r in hier.log.records] == [1]
    assert [r.tier for r in sub.log.records] == [None]
    assert hier.log.records[0].wire_bytes == 8


def test_delegate_refuses_already_tiered_records():
    sub = _PokingTransport()
    hier = HierarchicalTransport(tier0=sub, tier1="xla")
    with pytest.raises(RuntimeError, match="already carries"):
        hier._delegate(sub, 1, "poke", rec_tier=0)
    # the poisoned record was NOT copied into the outer log
    assert hier.log.records == []


# ---------------------------------------------------------------------------
# satellite: loadgen percentile semantics
# ---------------------------------------------------------------------------

class _Resp:
    def __init__(self, version):
        self.version = version


class _StubStore:
    def __init__(self, version=3):
        self.version = version


class _StubService:
    """Duck-typed service: synchronous submit with optional service time."""

    def __init__(self, service_s=0.0, fail=False, version=3):
        self.store = _StubStore(version)
        self.service_s = service_s
        self.fail = fail

    def submit(self, q):
        fut = concurrent.futures.Future()
        if self.fail:
            fut.set_exception(RuntimeError("stub refusal"))
            return fut
        if self.service_s:
            time.sleep(self.service_s)
        fut.set_result(_Resp(self.store.version))
        return fut


def test_loadgen_measures_from_scheduled_arrival():
    """Open loop: a slow service cannot hide queueing delay.  With all
    arrivals scheduled at t0 and a fixed per-request service time, the
    i-th latency grows ~linearly, so p99 >> p50 — a closed-loop
    (coordinated-omission) measurement would report them nearly equal."""
    svc = _StubService(service_s=0.002)
    rep = run_load(svc, n_requests=20, d=4, tick_s=0.0)
    assert rep.failed == 0 and rep.requests == 20
    assert rep.p99_ms > 1.5 * rep.p50_ms > 0.0
    # the last request waited behind ~all the others
    assert rep.p99_ms >= 0.5 * 20 * 2.0


def test_loadgen_all_failed_reports_zero_percentiles():
    reg = MetricsRegistry()
    rep = run_load(_StubService(fail=True), n_requests=5, d=4, metrics=reg)
    assert rep.failed == 5
    assert rep.p50_ms == rep.p99_ms == rep.mean_ms == 0.0
    assert rep.qps == 0.0
    assert reg.counter("serve_load_failed").value == 5
    assert reg.histogram("serve_latency_ms").count == 0


def test_loadgen_single_sample_percentiles_coincide():
    rep = run_load(_StubService(version=9), n_requests=1, d=4)
    assert rep.p50_ms == rep.p99_ms == rep.mean_ms
    assert rep.versions_min == rep.versions_max == 9
    assert rep.versions_monotonic and rep.n_versions == 1
    assert rep.staleness_max == 0


# ---------------------------------------------------------------------------
# satellite: span timing must use the monotonic clock
# ---------------------------------------------------------------------------

def test_no_wall_clock_timing_under_src():
    """``time.time()`` jumps with NTP adjustments; span math and latency
    measurements must use ``time.monotonic*``/``time.perf_counter``.
    (Mirrored as a ruff TID251 banned-api pin for environments with ruff.)
    """
    src = pathlib.Path(__file__).resolve().parent.parent / "src"
    offenders = [
        str(p) for p in sorted(src.rglob("*.py"))
        if "time.time(" in p.read_text()
    ]
    assert offenders == [], f"time.time() used in {offenders}"


# ---------------------------------------------------------------------------
# bounded buffers + exit flush (crash-surviving exports)
# ---------------------------------------------------------------------------

def test_tracer_buffers_drop_oldest_with_count():
    t = Tracer(max_spans=5, max_counters=3)
    for i in range(12):
        t.add_span(f"s{i}", 0.0, 1.0, track="tk")
        t.counter("c", float(i))
    spans = t.spans()
    assert len(spans) == 5 and t.dropped_spans == 7
    # drop-oldest: the survivors are the NEWEST five
    assert [e.name for e in spans] == [f"s{i}" for i in range(7, 12)]
    assert len(t.counters()) == 3 and t.dropped_counters == 9
    assert [c.value for c in t.counters()] == [9.0, 10.0, 11.0]
    # wall spans ride the same bound
    with t.span("w"):
        pass
    assert len(t.spans()) == 5 and t.dropped_spans == 8
    assert t.spans()[-1].name == "w"


def test_tracer_bounds_validate():
    with pytest.raises(ValueError):
        Tracer(max_spans=0)
    with pytest.raises(ValueError):
        Tracer(max_counters=0)


def test_exit_flush_requires_a_sink():
    from repro.obs import ExitFlush
    with pytest.raises(ValueError):
        ExitFlush()


def test_exit_flush_writes_once_and_is_idempotent(tmp_path):
    from repro.obs import ExitFlush
    t = Tracer()
    t.add_span("a", 0.0, 1.0, track="tk")
    reg = MetricsRegistry()
    reg.counter("x").inc(3)
    tp, mp = str(tmp_path / "t.json"), str(tmp_path / "m.jsonl")
    fl = ExitFlush(tracer=t, trace_path=tp, metrics=reg, metrics_path=mp,
                   run="r1")
    written = fl.flush()
    assert written == {"trace": tp, "metrics": mp}
    spans = [e for e in load_trace(tp) if e.get("ph") == "X"]
    assert [s["name"] for s in spans] == ["a"]
    rows = load_jsonl(mp)
    assert rows[-1]["name"] == "x" and rows[-1]["value"] == 3
    # second flush is a no-op: metrics JSONL must not double-append
    assert fl.flush() == {}
    assert len(load_jsonl(mp)) == len(rows)


def test_exit_flush_context_manager_flushes_on_exception(tmp_path):
    from repro.obs import ExitFlush
    t = Tracer()
    t.add_span("died", 0.0, 1.0, track="tk")
    tp = str(tmp_path / "t.json")
    with pytest.raises(RuntimeError):
        with ExitFlush(tracer=t, trace_path=tp):
            raise RuntimeError("chaos kill")
    spans = [e for e in load_trace(tp) if e.get("ph") == "X"]
    assert [s["name"] for s in spans] == ["died"]
