"""Serving subsystem (ISSUE 3): versioned store, sharded lookup, the
micro-batching service, the engine publish hook, and the serve bench gate.

Acceptance bars under test: served assignments bit-match the ``kernels/ref``
oracle for a pinned codebook version; a hot-swap under concurrent load never
serves a torn codebook and versions only move forward; the micro-batcher
flushes partial batches on deadline.  Multi-device lookup plans carry
``@pytest.mark.devices(n)`` so the 1-device CI leg skips them.
"""

import pathlib
import sys
import threading
import time

from repro.xla_flags import force_host_devices

force_host_devices(8)

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from repro.data import synthetic  # noqa: E402
from repro.engine import (ElasticMeshExecutor, GeometricDelayNetwork,  # noqa: E402
                          InstantNetwork, MeshExecutor, ResizeSchedule)
from repro.kernels import ref  # noqa: E402
from repro.launch import serve as serve_cli  # noqa: E402
from repro.serve import (CodebookStore, QuantizeService,  # noqa: E402
                         ShardedLookup, arrival_gaps_s, run_load)

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
from benchmarks import check_regression  # noqa: E402

KEY = jax.random.PRNGKey(7)
D, KAPPA = 16, 48


def _codebook(kappa=KAPPA, d=D, fold=0):
    return np.asarray(jax.random.normal(jax.random.fold_in(KEY, fold),
                                        (kappa, d)), np.float32)


def _queries(n, d=D, fold=100):
    return np.asarray(jax.random.normal(jax.random.fold_in(KEY, fold),
                                        (n, d)), np.float32)


# ---------------------------------------------------------------------------
# CodebookStore
# ---------------------------------------------------------------------------

def test_store_versions_strictly_monotonic():
    store = CodebookStore()
    assert store.version == 0 and len(store) == 0
    with pytest.raises(LookupError):
        store.latest()
    w = _codebook()
    s1 = store.publish(w, step=10)
    s2 = store.publish(2 * w, step=20)
    assert (s1.version, s2.version) == (1, 2)
    assert store.latest() is s2
    assert store.get(1) is s1 and store.get(99) is None
    # snapshots are immutable: the published array cannot be poked
    with pytest.raises(ValueError):
        s1.w[0, 0] = 123.0
    # publisher() plugs straight into on_window
    store.publisher()(7, 3 * w)
    assert store.version == 3 and store.latest().step == 7


def test_store_history_bounded_and_wait_for():
    store = CodebookStore(_codebook(), keep=3)
    for i in range(6):
        store.publish(_codebook(fold=i))
    assert store.version == 7 and len(store) == 3
    assert store.get(1) is None and store.get(7) is not None
    assert store.wait_for(7, timeout=0.01)
    assert not store.wait_for(99, timeout=0.01)
    with pytest.raises(ValueError):
        CodebookStore(keep=0)
    with pytest.raises(ValueError):
        store.publish(np.zeros(3))  # not (kappa, d)


def test_store_concurrent_publish_no_torn_reads():
    """Readers racing a publisher must always see (version, w) pairs that
    belong together — w filled with its own version number makes a torn
    snapshot directly visible."""
    store = CodebookStore(np.full((4, 4), 1.0, np.float32))
    stop = threading.Event()
    torn = []

    def reader():
        while not stop.is_set():
            snap = store.latest()
            if not np.all(snap.w == float(snap.version)):
                torn.append(snap.version)

    threads = [threading.Thread(target=reader) for _ in range(3)]
    for t in threads:
        t.start()
    for v in range(2, 200):
        store.publish(np.full((4, 4), float(v), np.float32))
    stop.set()
    for t in threads:
        t.join()
    assert not torn


# ---------------------------------------------------------------------------
# ShardedLookup
# ---------------------------------------------------------------------------

def test_lookup_direct_bitmatches_oracle():
    look = ShardedLookup(n_devices=1)
    z, w = _queries(37), _codebook()
    a, m = look.assign(z, w)
    ar, mr = ref.vq_assign_ref(z, w)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(ar))
    np.testing.assert_allclose(np.asarray(m), np.asarray(mr), rtol=1e-5)
    assert look.plan(KAPPA, D) == "direct"


@pytest.mark.devices(2)
@pytest.mark.parametrize("mode", ["shard_batch", "shard_kappa"])
def test_lookup_sharded_bitmatches_oracle(mode):
    look = ShardedLookup(n_devices=2, mode=mode)
    z, w = _queries(64), _codebook()
    a, m = look.assign(z, w)
    ar, mr = ref.vq_assign_ref(z, w)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(ar))
    np.testing.assert_allclose(np.asarray(m), np.asarray(mr), rtol=1e-5)


@pytest.mark.devices(8)
def test_lookup_shard_kappa_ragged_padding():
    """kappa not divisible by the shard count: sentinel pad rows never win."""
    look = ShardedLookup(n_devices=8, mode="shard_kappa")
    z, w = _queries(40), _codebook(kappa=13)  # 13 rows over 8 shards
    a, m = look.assign(z, w)
    ar, mr = ref.vq_assign_ref(z, w)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(ar))
    np.testing.assert_allclose(np.asarray(m), np.asarray(mr), rtol=1e-5)


@pytest.mark.devices(2)
def test_lookup_auto_routes_by_vmem_budget():
    tiny = ShardedLookup(n_devices=2, budget_bytes=256)
    big = ShardedLookup(n_devices=2)
    assert tiny.plan(KAPPA, D) == "shard_kappa"
    assert big.plan(KAPPA, D) == "shard_batch"


def test_lookup_validation():
    with pytest.raises(ValueError, match="unknown lookup mode"):
        ShardedLookup(mode="psum")
    with pytest.raises(ValueError, match="n_devices"):
        ShardedLookup(n_devices=len(jax.devices()) + 1)
    if len(jax.devices()) >= 2:
        look = ShardedLookup(n_devices=2, mode="shard_batch")
        with pytest.raises(ValueError, match="multiple"):
            look.assign(_queries(33), _codebook())  # 33 % 2 != 0
    with pytest.raises(ValueError, match="matching d"):
        ShardedLookup(n_devices=1).assign(_queries(8, d=4), _codebook())


# ---------------------------------------------------------------------------
# QuantizeService
# ---------------------------------------------------------------------------

def test_service_bitmatches_oracle_for_pinned_version():
    w = _codebook()
    store = CodebookStore(w)
    with QuantizeService(store, ShardedLookup(), max_delay_s=1e-3) as svc:
        z_single = _queries(1)[0]           # (d,) single-vector form
        z_bulk = _queries(29, fold=5)
        r1 = svc.quantize(z_single)
        r2 = svc.quantize(z_bulk)
    ar, mr = ref.vq_assign_ref(z_single[None], w)
    np.testing.assert_array_equal(r1.assign, np.asarray(ar))
    np.testing.assert_allclose(r1.mindist, np.asarray(mr), rtol=1e-5)
    ar, _ = ref.vq_assign_ref(z_bulk, w)
    np.testing.assert_array_equal(r2.assign, np.asarray(ar))
    assert r1.version == r2.version == 1
    assert r1.batch_rows >= 1 and r2.batch_rows >= 29


def test_service_deadline_flushes_partial_batch():
    store = CodebookStore(_codebook())
    svc = QuantizeService(store, ShardedLookup(n_devices=1),
                          max_batch=10_000, max_delay_s=0.05)
    with svc:
        t0 = time.monotonic()
        futs = [svc.submit(_queries(1)[0]) for _ in range(3)]
        resps = [f.result(timeout=10) for f in futs]
        waited = time.monotonic() - t0
    # far from full, so only the deadline can have flushed it
    assert svc.stats.deadline_flushes >= 1 and svc.stats.full_flushes == 0
    assert waited >= 0.04
    assert all(r.version == 1 for r in resps)
    assert svc.stats.requests == 3 and svc.stats.rows == 3


def test_service_full_batch_flushes_before_deadline():
    store = CodebookStore(_codebook())
    svc = QuantizeService(store, ShardedLookup(n_devices=1),
                          max_batch=64, max_delay_s=30.0)
    with svc:
        t0 = time.monotonic()
        futs = [svc.submit(_queries(16, fold=i)) for i in range(4)]
        for f in futs:
            f.result(timeout=10)
        waited = time.monotonic() - t0
    # 64 pending rows filled max_batch: no 30s deadline wait
    assert waited < 5.0
    assert svc.stats.full_flushes >= 1
    assert svc.stats.mean_fill >= 16


def test_service_stamps_queue_wait_within_latency():
    """``queued_s`` (submit to taken into a flush) lies in [0, latency_s]
    for every response; requests that fill a batch are taken before the
    deadline, and a lone request waits it out."""
    store = CodebookStore(_codebook())
    filling = [_queries(16, fold=i) for i in range(4)]
    lone = _queries(3, fold=50)
    with QuantizeService(store, ShardedLookup(n_devices=1), max_batch=64,
                         max_delay_s=0.5) as svc:
        futs = [svc.submit(z) for z in filling]
        resps = [f.result(timeout=10) for f in futs]
        resps.append(svc.quantize(lone))
    for r in resps:
        assert 0.0 <= r.queued_s <= r.latency_s
    assert all(r.queued_s < 0.5 for r in resps[:4])
    assert resps[-1].queued_s >= 0.5
    assert svc.stats.full_flushes == 1 and svc.stats.deadline_flushes == 1


FLUSH_CHILDREN = ("serve.gather", "serve.lookup", "serve.sync",
                  "serve.respond")


def test_flush_spans_reach_the_profiler_host_plane(tmp_path):
    """With no tracer given, the flush thread's spans still land on a
    ``jax.profiler`` trace's host plane, each child inside its flush."""
    from jax.profiler import ProfileData

    store = CodebookStore(_codebook())
    svc = QuantizeService(store, ShardedLookup(n_devices=1),
                          max_delay_s=1e-3).start()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for k in range(3):
            svc.quantize(_queries(2, fold=k))
        svc.stop()
    finally:
        jax.profiler.stop_trace()
    path, = tmp_path.rglob("*.xplane.pb")
    spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events
             if e.name.startswith("serve.")]
    names = {n for _, _, n in spans}
    assert {"serve.flush", "serve.batch_wait", "serve.idle_wait",
            *FLUSH_CHILDREN} <= names
    flushes = [(s, e) for s, e, n in spans if n == "serve.flush"]
    assert len(flushes) == 3
    for s, e, n in spans:
        if n in FLUSH_CHILDREN:
            assert any(fs <= s and e <= fe for fs, fe in flushes), n


def test_program_span_names_carry_their_layer(monkeypatch):
    """Every wall span the program opens is named ``<layer>.<what>``, and
    none is one of the benchmark's own span names, which mark the edges of
    a traced window."""
    import importlib.util
    import re

    root = pathlib.Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location(
        "bench_xplane", root / "bench" / "xplane.py")
    xplane = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, spec.name, xplane)
    spec.loader.exec_module(xplane)
    names = set()
    for path in (root / "src" / "repro").rglob("*.py"):
        names |= set(re.findall(r'\bspan\(\s*"([^"]+)"', path.read_text()))
    assert {"serve.flush", "engine.segment", "engine.compile",
            "loadgen.submit", "elastic.resize"} <= names
    layers = ("serve.", "engine.", "loadgen.", "elastic.")
    assert all(n.startswith(layers) for n in names), sorted(names)
    assert not names & set(xplane.HARNESS_SPANS)


def test_service_pads_to_mxu_alignment():
    store = CodebookStore(_codebook())
    svc = QuantizeService(store, ShardedLookup(n_devices=1),
                          max_delay_s=1e-3, batch_align=128)
    with svc:
        svc.quantize(_queries(3, fold=9))
    assert svc.stats.padded_rows == 125  # 3 -> one aligned 128 block


def test_service_empty_store_fails_request_not_service():
    store = CodebookStore()
    with QuantizeService(store, ShardedLookup(n_devices=1),
                         max_delay_s=1e-3) as svc:
        with pytest.raises(LookupError):
            svc.quantize(_queries(1)[0])
        # the flush loop survives the fault; a publish heals the service
        store.publish(_codebook())
        assert svc.quantize(_queries(1)[0]).version == 1
    assert svc.stats.failed == 1


def test_service_submit_validation_and_lifecycle():
    store = CodebookStore(_codebook())
    svc = QuantizeService(store, ShardedLookup(n_devices=1))
    with pytest.raises(RuntimeError, match="not running"):
        svc.submit(_queries(1)[0])
    with svc:
        with pytest.raises(ValueError, match="rows, d"):
            svc.submit(np.zeros((2, 3, 4)))
        with pytest.raises(RuntimeError, match="already running"):
            svc.start()
    with pytest.raises(ValueError, match="max_delay_s"):
        QuantizeService(store, ShardedLookup(n_devices=1), max_delay_s=-1)


def test_service_survives_cancelled_future():
    """cancel() on a queued request must not kill the flush thread or the
    requests coalesced into the same batch."""
    store = CodebookStore(_codebook())
    with QuantizeService(store, ShardedLookup(n_devices=1),
                         max_batch=10_000, max_delay_s=0.05) as svc:
        doomed = svc.submit(_queries(1)[0])
        assert doomed.cancel()
        live = svc.submit(_queries(2, fold=3))
        resp = live.result(timeout=10)
        assert resp.version == 1
        # the service still works after the cancelled flush
        assert svc.quantize(_queries(1, fold=4)[0]).version == 1


def test_store_publish_does_not_freeze_callers_array():
    w = _codebook().copy()
    store = CodebookStore()
    store.publish(w)
    w[0, 0] = 42.0  # caller keeps a writable array...
    assert store.latest().w[0, 0] != 42.0  # ...and the snapshot a copy


def test_service_hot_swap_under_concurrent_load():
    """The acceptance bar: concurrent publishes never tear a response —
    every answer bit-matches the oracle on the exact version it reports —
    and versions served only move forward."""
    n_versions, n_clients, n_reqs = 30, 4, 25
    store = CodebookStore(_codebook(fold=1), keep=n_versions + 1)
    results: dict[int, list] = {i: [] for i in range(n_clients)}
    errors: list[Exception] = []

    with QuantizeService(store, ShardedLookup(), max_delay_s=5e-4) as svc:
        stop = threading.Event()

        def publisher():
            for v in range(2, n_versions + 2):
                store.publish(_codebook(fold=v))
                time.sleep(1e-3)
            stop.set()

        def client(i):
            try:
                for j in range(n_reqs):
                    z = _queries(3, fold=1000 + i * n_reqs + j)
                    results[i].append((z, svc.quantize(z)))
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = ([threading.Thread(target=publisher)]
                   + [threading.Thread(target=client, args=(i,))
                      for i in range(n_clients)])
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    assert not errors
    served_versions = set()
    for i in range(n_clients):
        versions = [r.version for _, r in results[i]]
        # in-order clients see non-decreasing versions (store is monotone
        # and flushes happen in submission order)
        assert versions == sorted(versions)
        served_versions.update(versions)
        for z, r in results[i]:
            snap = store.get(r.version)
            assert snap is not None, "served a version the store never had"
            ar, _ = ref.vq_assign_ref(z, snap.w)
            np.testing.assert_array_equal(r.assign, np.asarray(ar))
    assert len(served_versions) > 1, "load never overlapped a hot swap"


# ---------------------------------------------------------------------------
# engine publish hook (on_window)
# ---------------------------------------------------------------------------

def _setup(m, n=300, d=8, kappa=16):
    kd, kw = jax.random.split(KEY)
    data = synthetic.replicate_stream(kd, m, n=n, d=d)
    return data, data[:, :100], synthetic.kmeanspp_init(
        kw, data.reshape(-1, d), kappa)


@pytest.mark.parametrize("publish_every", [1, 7])
def test_mesh_on_window_identical_numerics(publish_every):
    data, ev, w0 = _setup(1)
    plain = MeshExecutor(network=InstantNetwork()).run(
        "delta", w0, data, ev, tau=10)
    pubs = []
    ex = MeshExecutor(network=InstantNetwork(),
                      on_window=lambda wi, w: pubs.append((wi, np.asarray(w))),
                      publish_every=publish_every)
    res = ex.run("delta", w0, data, ev, tau=10)
    np.testing.assert_allclose(np.asarray(res.distortion),
                               np.asarray(plain.distortion), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(res.wall_ticks),
                                  np.asarray(plain.wall_ticks))
    n_windows = data.shape[1] // 10
    windows = [wi for wi, _ in pubs]
    assert windows[-1] == n_windows and windows == sorted(set(windows))
    np.testing.assert_allclose(pubs[-1][1], np.asarray(res.w_shared),
                               rtol=1e-6)
    with pytest.raises(ValueError, match="publish_every"):
        MeshExecutor(publish_every=0)


@pytest.mark.devices(4)
def test_elastic_on_window_global_windows_across_resizes():
    data, ev, w0 = _setup(4)
    store = CodebookStore()
    sched = ResizeSchedule([(10, 2), (20, 4)])
    ex = ElasticMeshExecutor(sched, network=InstantNetwork(),
                             on_window=store.publisher(), publish_every=4)
    res = ex.run("delta", w0, data, ev, tau=10)
    steps = [store.get(v).step for v in range(1, store.version + 1)]
    assert steps == sorted(steps), "window tags must be global + monotone"
    assert len(ex.resize_events) == 2
    baseline = ElasticMeshExecutor(sched, network=InstantNetwork()).run(
        "delta", w0, data, ev, tau=10)
    np.testing.assert_allclose(np.asarray(res.distortion),
                               np.asarray(baseline.distortion), rtol=1e-6)
    np.testing.assert_allclose(store.latest().w, np.asarray(res.w_shared),
                               rtol=1e-6)
    # clearing the hook must actually clear it on the cached per-M
    # executors: a re-run may not keep publishing into the old store
    ex.on_window = None
    v_before = store.version
    ex.run("delta", w0, data, ev, tau=10)
    assert store.version == v_before


# ---------------------------------------------------------------------------
# loadgen
# ---------------------------------------------------------------------------

def test_loadgen_geometric_arrivals_and_report():
    gaps = arrival_gaps_s(GeometricDelayNetwork(0.5), 500, tick_s=1e-3,
                          key=KEY)
    assert gaps.shape == (500,) and np.all(gaps >= 1e-3)  # round >= tau=1
    assert gaps.max() > 1e-3  # geometric extras actually drawn

    store = CodebookStore(_codebook())
    with QuantizeService(store, ShardedLookup(), max_delay_s=1e-3) as svc:
        rep = run_load(svc, n_requests=50, d=D, rows_per_request=2,
                       network=GeometricDelayNetwork(0.5), tick_s=1e-4,
                       key=KEY)
    assert rep.failed == 0 and rep.requests == 50 and rep.rows == 100
    assert rep.qps > 0 and rep.p50_ms <= rep.p99_ms
    assert rep.versions_min == rep.versions_max == 1
    assert rep.versions_monotonic and rep.staleness_max == 0
    assert "50 req" in rep.summary()


# ---------------------------------------------------------------------------
# serve benchmark gate (mirrors the engine-gate unit tests)
# ---------------------------------------------------------------------------

def _serve_doc(speedup=100.0, failed=0, monotonic=True):
    return {"suite": "serve", "results": [
        {"kind": "speedup", "m": 8, "kappa": 64, "d": 32, "speedup": speedup},
        {"kind": "hotswap", "failed": failed,
         "versions_monotonic": monotonic, "versions_served": [1, 5],
         "staleness_max": 1},
    ]}


def test_serve_gate_pass_and_regression():
    ok, msgs = check_regression.check_serve(_serve_doc(100), _serve_doc(90))
    assert ok, msgs
    ok, msgs = check_regression.check_serve(_serve_doc(100), _serve_doc(50))
    assert not ok and any("FAIL" in m for m in msgs)


def test_serve_gate_absolute_floor_and_hotswap():
    ok, _ = check_regression.check_serve(_serve_doc(4.0), _serve_doc(3.5))
    assert not ok  # below the 4x serving bar even if relative drop is small
    ok, msgs = check_regression.check_serve(_serve_doc(), _serve_doc(failed=2))
    assert not ok and any("hot-swap" in m for m in msgs)
    ok, _ = check_regression.check_serve(_serve_doc(),
                                         _serve_doc(monotonic=False))
    assert not ok


def test_serve_gate_config_mismatch_and_dispatch():
    bad = _serve_doc()
    bad["results"][0]["kappa"] = 999
    with pytest.raises(ValueError, match="config mismatch"):
        check_regression.check_serve(_serve_doc(), bad)
    with pytest.raises(ValueError, match="speedup"):
        check_regression.check_serve({"suite": "serve", "results": []},
                                     _serve_doc())
    # main() dispatches on the suite field and rejects mixed suites
    import json
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        base, fresh = f"{td}/b.json", f"{td}/f.json"
        with open(base, "w") as f:
            json.dump(_serve_doc(), f)
        with open(fresh, "w") as f:
            json.dump(_serve_doc(speedup=95), f)
        assert check_regression.main(["--baseline", base,
                                      "--fresh", fresh]) == 0
        with open(fresh, "w") as f:
            json.dump({"suite": "engine", "results": []}, f)
        assert check_regression.main(["--baseline", base,
                                      "--fresh", fresh]) == 2


# ---------------------------------------------------------------------------
# CLI + bench plumbing
# ---------------------------------------------------------------------------

def test_serve_cli_vq_smoke(capsys):
    rc = serve_cli.main(["--mode", "vq", "--smoke", "--requests", "40",
                         "--dim", "8", "--kappa", "8", "--tick-ms", "0"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "0 failed" in out and "plan=" in out


def test_serve_cli_codebook_larger_than_training_stream(capsys):
    """The initial codebook samples kappa points even where --points (the
    trainer's per-worker stream) holds fewer than kappa in all."""
    rc = serve_cli.main(["--mode", "vq", "--smoke", "--requests", "20",
                         "--dim", "8", "--kappa", "2048", "--tick-ms", "0"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "0 failed" in out


def test_serve_cli_train_publish_smoke(capsys):
    rc = serve_cli.main(["--mode", "vq", "--smoke", "--requests", "30",
                         "--dim", "8", "--kappa", "8", "--train-publish",
                         "--points", "100", "--tick-ms", "0.2"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "trainer published" in out


def test_suite_out_path_derivation():
    from benchmarks.run import suite_out_path
    assert suite_out_path("", "engine", multi=True) == "BENCH_engine.json"
    assert suite_out_path("F.json", "engine", multi=False) == "F.json"
    assert suite_out_path("F.json", "engine", multi=True) == "F.engine.json"
    assert suite_out_path("F.json", "serve", multi=True) == "F.serve.json"
    assert suite_out_path("FRESH", "elastic",
                          multi=True) == "FRESH.elastic.json"
