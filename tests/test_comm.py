"""Communication-layer tests (ISSUE 4): every MergeStrategy x Transport
combination against the XlaTransport oracle, wire-byte accounting, the
VMEM-routed mesh inner loop, the comm regression gate, and the CLI.

Runs on both CI legs: the M=1 cells exercise degenerate (no-wire) meshes,
the M=8 cells the real collective paths (``@pytest.mark.devices``).
"""

from repro.xla_flags import force_host_devices

force_host_devices(8)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from repro import comm  # noqa: E402
from repro.comm import (CommLog, CommRecord, SparseTransport,  # noqa: E402
                        XlaTransport, get_transport, ring_wire_bytes)
from repro.core import schemes  # noqa: E402
from repro.data import synthetic  # noqa: E402
from repro.engine import (InstantNetwork, MeshExecutor,  # noqa: E402
                          get_network)
from repro.engine import merge as merge_lib  # noqa: E402

KEY = jax.random.PRNGKey(42)
TAU = 10
D, KAPPA = 8, 16
# k/kappa = 0.25: k = kappa/4 entries kept of the kappa*d displacement —
# the acceptance point where sparse wire must be >= 4x under dense
FRAC_Q = (KAPPA // 4) / (KAPPA * D)


def _setup(m, n=400):
    kd, kw = jax.random.split(KEY)
    data = synthetic.replicate_stream(kd, m, n=n, d=D)
    eval_data = data[:, :200]
    w0 = synthetic.kmeanspp_init(kw, data.reshape(-1, D), KAPPA)
    return data, eval_data, w0


def _run(scheme, m, transport, n=400, **kw):
    data, eval_data, w0 = _setup(m, n)
    ex = MeshExecutor(network=InstantNetwork(),
                      transport=transport, **kw)
    res = ex.run(scheme, w0, data, eval_data, tau=TAU,
                 key=jax.random.fold_in(KEY, 9))
    return res, ex


# ---------------------------------------------------------------------------
# factory + API surface
# ---------------------------------------------------------------------------

def test_get_transport_factory():
    assert get_transport("xla").name == "xla"
    assert get_transport("ring").name == "ring"
    sp = get_transport("sparse", frac=0.5)
    assert sp.name == "sparse" and sp.stateful
    # instances pass through (executors accept either spelling)
    assert get_transport(sp) is sp
    with pytest.raises(ValueError, match="unknown transport"):
        get_transport("pigeon")
    with pytest.raises(ValueError, match="frac"):
        get_transport("sparse", frac=0.0)
    with pytest.raises(ValueError, match="unknown reduce op"):
        XlaTransport().all_reduce(jnp.zeros(3), "workers", op="max")


def test_get_merge_factory_and_transport_plumbing():
    assert merge_lib.get_merge("delta_sparse").name == "delta_sparse"
    assert isinstance(merge_lib.get_merge("delta_sparse").transport,
                      SparseTransport)
    t = get_transport("ring")
    assert merge_lib.get_merge("delta", transport=t).transport is t
    with pytest.raises(ValueError, match="unknown merge"):
        merge_lib.get_merge("gossip")


def test_comm_log_summarize():
    log = CommLog()
    log.append(CommRecord(op="sum", transport="xla", axis="w",
                          participants=4, logical_bytes=100, wire_bytes=150,
                          calls=10))
    log.append(CommRecord(op="mean", transport="xla", axis="w",
                          participants=4, logical_bytes=4, wire_bytes=6,
                          calls=10, tag="eval"))
    s = CommLog.summarize(log.records)
    assert s["wire_bytes"] == 1560 and s["logical_bytes"] == 1040
    assert s["by_tag"]["merge"]["wire_bytes"] == 1500
    assert s["by_tag"]["eval"]["wire_bytes"] == 60
    mark = log.mark()
    assert log.since(mark) == []


def test_comm_log_bounded_with_absolute_marks():
    """The log drops oldest records past max_records; marks are absolute,
    so since() stays correct across trims (no unbounded growth in a
    long-lived serve/train-publish loop)."""
    rec = CommRecord(op="sum", transport="xla", axis="w", participants=2,
                     logical_bytes=8, wire_bytes=8)
    log = CommLog(max_records=4)
    for _ in range(10):
        log.append(rec)
    assert len(log.records) == 4 and log.mark() == 10
    m = log.mark()
    log.extend([rec, rec])
    assert len(log.records) == 4                 # still bounded
    assert len(log.since(m)) == 2                # the two new ones
    assert log.since(0) == log.records           # old window: what's left
    with pytest.raises(ValueError, match="max_records"):
        CommLog(max_records=0)


def test_ring_wire_convention():
    assert ring_wire_bytes(1024, 1) == 0       # one participant: no wire
    assert ring_wire_bytes(1024, 8) == 2 * 7 * 1024 // 8


# ---------------------------------------------------------------------------
# equivalence suite: MergeStrategy x Transport vs the XlaTransport oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "m", [1, pytest.param(8, marks=pytest.mark.devices(8))])
@pytest.mark.parametrize("scheme", ["average", "delta", "async_delta"])
def test_ring_matches_xla_exactly(scheme, m):
    """Dense transports are numerics-identical: same schemes, same bytes,
    same bits (on CPU the ring rides its XLA fallback — the contract the
    TPU Pallas path is tested against)."""
    base, _ = _run(scheme, m, "xla")
    ring, ex = _run(scheme, m, "ring")
    np.testing.assert_array_equal(np.asarray(base.distortion),
                                  np.asarray(ring.distortion))
    np.testing.assert_array_equal(np.asarray(base.w_shared),
                                  np.asarray(ring.w_shared))
    merge = ex.last_comm["by_tag"]["merge"]
    if m == 1:
        assert merge["wire_bytes"] == 0
    else:
        assert merge["wire_bytes"] > 0


@pytest.mark.devices(4)
@pytest.mark.parametrize("shape", [(16, 8), (3, 700), (1024, 128)])
def test_ring_kernel_matches_psum(shape):
    """The Pallas ring kernel itself, under TPU interpret mode on 4 CPU
    devices (simulated RDMAs and semaphores): padding to whole tiles and
    the two ring phases give psum's sums up to summation order."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("workers",))
    x = jax.random.normal(KEY, (4, *shape))

    def both(x_l):
        return (comm.ring_all_reduce(x_l[0], "workers", interpret=True)[None],
                jax.lax.psum(x_l[0], "workers")[None])

    ring, ref = jax.jit(jax.shard_map(
        both, mesh=mesh, in_specs=P("workers"),
        out_specs=(P("workers"), P("workers")), check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(ref),
                               rtol=1e-6, atol=1e-5)


@pytest.mark.devices(4)
def test_ring_kernel_transport_matches_xla():
    """``RingTransport(use_pallas=True)`` through the mesh executor: the
    interpreted kernel carries the merges and the eval means, agreeing
    with the XLA transport and charging the same wire."""
    base, ex_xla = _run("delta", 4, "xla", n=20)
    ring, ex = _run("delta", 4, comm.RingTransport(use_pallas=True), n=20)
    np.testing.assert_allclose(np.asarray(ring.distortion),
                               np.asarray(base.distortion), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ring.w_shared),
                               np.asarray(base.w_shared), atol=1e-5)
    assert (ex.last_comm["by_tag"]["merge"]["wire_bytes"]
            == ex_xla.last_comm["by_tag"]["merge"]["wire_bytes"])


@pytest.mark.parametrize(
    "m", [1, pytest.param(8, marks=pytest.mark.devices(8))])
@pytest.mark.parametrize("scheme", ["average", "delta", "async_delta"])
def test_sparse_full_density_matches_xla(scheme, m):
    """frac=1.0 keeps everything: the gathered-scatter-add sum must agree
    with the dense all-reduce (only floating-sum order can differ)."""
    base, _ = _run(scheme, m, "xla")
    sparse, _ = _run(scheme, m, get_transport("sparse", frac=1.0))
    np.testing.assert_allclose(np.asarray(base.distortion),
                               np.asarray(sparse.distortion),
                               rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize(
    "m", [1, pytest.param(8, marks=pytest.mark.devices(8))])
@pytest.mark.parametrize("scheme", ["average", "delta", "async_delta"])
def test_sparse_low_density_distortion_bound(scheme, m):
    """At k/kappa = 0.25 the error-feedback compressed merges must stay
    within 25% of the dense final distortion and still converge."""
    base, _ = _run(scheme, m, "xla")
    sparse, _ = _run(scheme, m, get_transport("sparse", frac=FRAC_Q))
    curve = np.asarray(sparse.distortion)
    assert np.all(np.isfinite(curve))
    assert curve[-1] < curve[0]                      # it converges
    gap = curve[-1] / float(base.distortion[-1]) - 1.0
    assert abs(gap) < 0.25, f"sparse final C off dense by {gap:+.3f}"


@pytest.mark.devices(8)
def test_sparse_average_rides_dense():
    """Means are not compressed (absolute values don't concentrate): the
    average scheme over SparseTransport is bit-identical to dense."""
    base, _ = _run("average", 8, "xla")
    sparse, ex = _run("average", 8, get_transport("sparse", frac=FRAC_Q))
    np.testing.assert_array_equal(np.asarray(base.distortion),
                                  np.asarray(sparse.distortion))
    # and its merge wire is the dense figure, not the top-k one
    n_windows = 400 // TAU
    dense_per_window = ring_wire_bytes(4 * KAPPA * D, 8)
    assert (ex.last_comm["by_tag"]["merge"]["wire_bytes"]
            == n_windows * dense_per_window)


def test_mesh_default_transport_is_oracle_exact():
    """The refactor is invisible at the default: mesh delta on XlaTransport
    still equals the scheme_delta oracle."""
    data, eval_data, w0 = _setup(1)
    oracle = schemes.scheme_delta(w0, data, eval_data, tau=TAU)
    res, _ = _run("delta", 1, None)
    np.testing.assert_allclose(np.asarray(res.distortion),
                               np.asarray(oracle.distortion),
                               rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# wire-byte accounting (measured, replayed on cache hits)
# ---------------------------------------------------------------------------

@pytest.mark.devices(8)
def test_wire_bytes_closed_form_and_replay():
    m, n = 8, 400
    n_windows = n // TAU
    res, ex = _run("delta", m, "xla")
    merge = ex.last_comm["by_tag"]["merge"]
    logical = 4 * KAPPA * D
    assert merge["logical_bytes"] == n_windows * logical
    assert merge["wire_bytes"] == n_windows * ring_wire_bytes(logical, m)
    assert merge["calls"] == n_windows
    # eval traffic is tagged separately and tiny
    assert ex.last_comm["by_tag"]["eval"]["logical_bytes"] == n_windows * 4
    # a second run hits the compile cache; the replayed records must give
    # the same per-run summary (accounting survives caching)
    first = ex.last_comm
    data, eval_data, w0 = _setup(m)
    ex.run("delta", w0, data, eval_data, tau=TAU)
    assert ex.last_comm == first


@pytest.mark.devices(8)
def test_sparse_wire_reduction_at_quarter_kappa():
    """The ISSUE-4 acceptance inequality, measured: sparse merge wire >= 4x
    below dense at k/kappa = 0.25."""
    _, dense = _run("delta", 8, "xla")
    _, sparse = _run("delta", 8, get_transport("sparse", frac=FRAC_Q))
    dw = dense.last_comm["by_tag"]["merge"]["wire_bytes"]
    sw = sparse.last_comm["by_tag"]["merge"]["wire_bytes"]
    assert dw / sw >= 4.0, f"sparse reduction {dw / sw:.2f}x < 4x"


def test_single_worker_moves_no_wire():
    _, ex = _run("delta", 1, "xla")
    assert ex.last_comm["by_tag"]["merge"]["wire_bytes"] == 0
    assert ex.last_comm["by_tag"]["merge"]["logical_bytes"] > 0


@pytest.mark.devices(4)
def test_bandwidth_network_charges_measured_bytes():
    """FixedLatencyNetwork(bytes_per_tick=...) stretches the wall clock by
    the transport's MEASURED per-window wire bytes."""
    data, eval_data, w0 = _setup(4)
    free = MeshExecutor(network=InstantNetwork()).run(
        "delta", w0, data, eval_data, tau=TAU)
    per_window = ring_wire_bytes(4 * KAPPA * D, 4)
    net = get_network("fixed", latency_ticks=0, bytes_per_tick=per_window)
    taxed_ex = MeshExecutor(network=net)
    taxed = taxed_ex.run("delta", w0, data, eval_data, tau=TAU)
    # same merges, same curve values; each window pays exactly 1 extra tick
    np.testing.assert_allclose(np.asarray(free.distortion),
                               np.asarray(taxed.distortion), rtol=1e-6)
    assert int(taxed.wall_ticks[0]) == TAU + 1
    assert net.transfer_ticks(0) == 0
    assert get_network("fixed", latency_ticks=0).transfer_ticks(1 << 20) == 0
    with pytest.raises(ValueError, match="bytes_per_tick"):
        get_network("fixed", bytes_per_tick=-1)


@pytest.mark.devices(8)
def test_elastic_late_delta_rides_comm_accounting():
    from repro.engine import ElasticMeshExecutor, ResizeSchedule
    data, eval_data, w0 = _setup(8)
    ex = ElasticMeshExecutor(ResizeSchedule([(2, 4)]),
                             network=InstantNetwork())
    ex.run("delta", w0, data, eval_data, tau=TAU)
    late = ex.last_comm["by_tag"].get("late_delta")
    assert late is not None and late["wire_bytes"] == 4 * KAPPA * D
    assert ex.last_comm["by_tag"]["merge"]["wire_bytes"] > 0


# ---------------------------------------------------------------------------
# VMEM-routed mesh inner loop (ROADMAP: larger-than-VMEM codebooks)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "m", [1, pytest.param(4, marks=pytest.mark.devices(4))])
def test_mesh_routed_blocked_parity_kappa_gt_bk(m):
    """kappa > bk with a tiny VMEM budget forces the blocked-assign +
    segment-sum fallback inside the mesh inner loop; the run must be
    bit-compatible with the fused-kernel path (batch-of-one: no
    accumulation-order freedom)."""
    kappa = 192                       # > bk=128: codebook streams in tiles
    kd, kw = jax.random.split(KEY)
    data = synthetic.replicate_stream(kd, m, n=200, d=D)
    eval_data = data[:, :100]
    w0 = synthetic.kmeanspp_init(kw, data.reshape(-1, D), kappa)
    fused = MeshExecutor(network=InstantNetwork()).run(
        "delta", w0, data, eval_data, tau=TAU)
    from repro.kernels import ops
    assert not ops.delta_fits_vmem(kappa, D, budget_bytes=1024)
    routed = MeshExecutor(network=InstantNetwork(),
                          vmem_budget_bytes=1024).run(
        "delta", w0, data, eval_data, tau=TAU)
    np.testing.assert_allclose(np.asarray(fused.distortion),
                               np.asarray(routed.distortion),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(fused.w_shared),
                               np.asarray(routed.w_shared),
                               rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# regression-gate units (benchmarks/check_regression.py, comm suite)
# ---------------------------------------------------------------------------

def _comm_doc(wire_delta=17920, reduction=4.0, parity=1.0):
    cell = {"kind": "cell", "scheme": "delta", "transport": "xla",
            "m": 8, "n": 200, "d": 8, "kappa": 16, "tau": 10,
            "sparse_frac": None, "wall_s": 0.01,
            "merge_wire_bytes": wire_delta, "merge_logical_bytes": 10240,
            "final_C": 0.02}
    return {"suite": "comm", "results": [
        cell,
        {"kind": "sparse_reduction", "m": 8, "kappa": 16, "d": 8,
         "sparse_frac": 0.03125, "reduction": reduction},
        {"kind": "ring_parity", "m": 8,
         "parity": parity if isinstance(parity, dict) else
         {"average": parity, "delta": parity, "async_delta": parity}},
    ]}


def test_comm_gate_passes_identical():
    from benchmarks.check_regression import check_comm
    ok, msgs = check_comm(_comm_doc(), _comm_doc())
    assert ok, msgs


def test_comm_gate_fails_on_wire_drift():
    from benchmarks.check_regression import check_comm
    ok, msgs = check_comm(_comm_doc(), _comm_doc(wire_delta=17921))
    assert not ok and any("wire bytes drifted" in m for m in msgs)


def test_comm_gate_fails_below_sparse_floor():
    from benchmarks.check_regression import check_comm
    ok, msgs = check_comm(_comm_doc(), _comm_doc(reduction=3.2))
    assert not ok and any("below the 4x bar" in m for m in msgs)


def test_comm_gate_fails_on_ring_parity_regression():
    from benchmarks.check_regression import check_comm
    # a genuine ring slowdown hits EVERY scheme leg -> min regression 2x
    ok, msgs = check_comm(_comm_doc(parity=1.0), _comm_doc(parity=2.0))
    assert not ok and any("parity" in m for m in msgs)


def test_comm_gate_tolerates_single_leg_parity_noise():
    from benchmarks.check_regression import check_comm
    # one jittery leg on an oversubscribed host is NOT a regression
    fresh = _comm_doc(parity={"average": 2.0, "delta": 1.0,
                              "async_delta": 1.0})
    ok, msgs = check_comm(_comm_doc(parity=1.0), fresh)
    assert ok, msgs


def test_comm_gate_rejects_config_mismatch():
    from benchmarks.check_regression import check_comm
    fresh = _comm_doc()
    fresh["results"][0]["n"] = 400
    with pytest.raises(ValueError, match="regenerate the baseline"):
        check_comm(_comm_doc(), fresh)


# ---------------------------------------------------------------------------
# CLI (launch/train.py --transport)
# ---------------------------------------------------------------------------

def test_train_cli_transport_smoke(capsys):
    from repro.launch import train
    rc = train.main(["--mode", "vq", "--executor", "mesh", "--workers", "1",
                     "--points", "100", "--scheme", "delta",
                     "--transport", "sparse", "--compress-frac", "0.25"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "transport=sparse" in out and "comm[sparse]" in out


def test_train_cli_transport_needs_mesh(capsys):
    from repro.launch import train
    rc = train.main(["--mode", "vq", "--executor", "sim",
                     "--transport", "ring"])
    assert rc == 2
    assert "--executor mesh" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# stateful-transport composition (review-fix regressions)
# ---------------------------------------------------------------------------

@pytest.mark.devices(8)
def test_published_sparse_carries_residual_across_chunks():
    """The publish path must thread the error-feedback residual across its
    host-level chunks — same numerics as the unpublished run."""
    data, eval_data, w0 = _setup(8)
    plain, _ = _run("delta", 8, get_transport("sparse", frac=FRAC_Q))
    published = MeshExecutor(
        network=InstantNetwork(),
        transport=get_transport("sparse", frac=FRAC_Q),
        on_window=lambda *_: None, publish_every=3).run(
        "delta", w0, data, eval_data, tau=TAU)
    np.testing.assert_array_equal(np.asarray(plain.distortion),
                                  np.asarray(published.distortion))
    np.testing.assert_array_equal(np.asarray(plain.w_shared),
                                  np.asarray(published.w_shared))


def test_sparse_delta_merge_rejects_conflicting_frac():
    t = get_transport("sparse", frac=0.5)
    with pytest.raises(ValueError, match="conflicts"):
        merge_lib.SparseDeltaMerge(t, frac=0.25)
    # no frac, or a matching one, is fine
    assert merge_lib.SparseDeltaMerge(t).transport is t
    assert merge_lib.SparseDeltaMerge(t, frac=0.5).transport is t


@pytest.mark.devices(2)
def test_window_step_rejects_delta_over_stateful_transport():
    from repro.configs import registry
    from repro.optim import optimizers
    from repro.training import steps as steps_lib
    cfg = registry.get_smoke_config("granite_8b")
    mesh = jax.make_mesh((2, 1, 1), ("pod", "data", "model"))
    with pytest.raises(ValueError, match="DELTA_SPARSE instead"):
        steps_lib.make_window_step(
            cfg, optimizers.sgd(0.05), mesh, tau=2,
            merge=steps_lib.Merge.DELTA, transport="sparse")


@pytest.mark.devices(2)
def test_window_step_async_delta_over_sparse_transport():
    """ASYNC_DELTA x SparseTransport: init_window_state seeds the joint
    {own, comm} carry and a window runs finite (the crash the review
    found)."""
    from repro.configs import registry
    from repro.models import common as model_common
    from repro.optim import optimizers
    from repro.training import steps as steps_lib
    model_common.set_run_options(mesh=None)
    cfg = registry.get_smoke_config("granite_8b")
    mesh = jax.make_mesh((2, 1, 1), ("pod", "data", "model"))
    opt = optimizers.sgd(0.05)
    tsp = get_transport("sparse", frac=0.25)
    step = steps_lib.make_window_step(
        cfg, opt, mesh, tau=2, merge=steps_lib.Merge.ASYNC_DELTA,
        merge_axis="pod", transport=tsp)
    state = steps_lib.init_window_state(
        cfg, opt, KEY, steps_lib.Merge.ASYNC_DELTA, transport=tsp)
    assert set(state["delta_prev"]) == {"own", "comm"}
    toks = jax.random.randint(KEY, (2, 4, 8), 0, cfg.vocab)
    with mesh:
        out, metrics = jax.jit(step)(state, {"tokens": toks, "labels": toks})
    assert bool(jnp.isfinite(metrics["loss"]))
    assert set(out["delta_prev"]) == {"own", "comm"}


# ---------------------------------------------------------------------------
# the LM window step rides the same implementations (spot check)
# ---------------------------------------------------------------------------

def test_window_step_sparse_strategy_is_shared():
    """DELTA_SPARSE's residual init comes from the shared SparseDeltaMerge,
    and the strategy's leaf math is comm.sparse.sparse_allsum (one
    implementation for the LM window step and the VQ engine)."""
    params = {"w": jnp.ones((4, 4)), "b": jnp.ones((4,))}
    strat = merge_lib.SparseDeltaMerge(frac=0.5)
    state = strat.init_state(params)
    assert set(state) == {"w", "b"}
    assert all(leaf.dtype == jnp.float32
               for leaf in jax.tree.leaves(state))
    assert comm.sparse_allsum is not None
