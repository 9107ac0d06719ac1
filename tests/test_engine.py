"""Engine/oracle equivalence + mesh validation (ISSUE 1 acceptance tests).

``MeshExecutor`` runs the schemes as real SPMD programs over an
8-way forced-host-platform device mesh; every distortion curve must match
the single-device oracles in ``core.schemes`` / ``core.async_vq`` to
tolerance, on a 1-device mesh and on the full 8-way mesh.
"""

import re

from repro.xla_flags import force_host_devices

# Flag must be set before jax initializes (the keras distribution_lib_test
# idiom); tests/conftest.py also sets it, but keep the module standalone.
force_host_devices(8)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from repro.core import async_vq, schemes  # noqa: E402
from repro.data import synthetic  # noqa: E402
from repro import engine  # noqa: E402
from repro.engine import (GeometricDelayNetwork, InstantNetwork,  # noqa: E402
                          MeshExecutor, SimExecutor, ThreadExecutor,
                          get_executor, get_network, make_worker_mesh)
from repro.engine import mesh as mesh_lib  # noqa: E402

KEY = jax.random.PRNGKey(42)
TAU = 10


def _setup(m, n=600, d=8, kappa=16):
    kd, kw = jax.random.split(KEY)
    data = synthetic.replicate_stream(kd, m, n=n, d=d)
    eval_data = data[:, :200]
    w0 = synthetic.kmeanspp_init(kw, data.reshape(-1, d), kappa)
    return data, eval_data, w0


def _assert_curves_match(a, b, rtol=1e-4):
    np.testing.assert_allclose(np.asarray(a.wall_ticks),
                               np.asarray(b.wall_ticks))
    np.testing.assert_allclose(np.asarray(a.distortion),
                               np.asarray(b.distortion), rtol=rtol, atol=1e-6)


# ---------------------------------------------------------------------------
# engine/oracle equivalence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "m", [1, pytest.param(8, marks=pytest.mark.devices(8))])
def test_mesh_delta_matches_oracle(m):
    """Acceptance: MeshExecutor delta curves == scheme_delta, M=1 and M=8."""
    data, eval_data, w0 = _setup(m)
    oracle = schemes.scheme_delta(w0, data, eval_data, tau=TAU)
    mesh_ex = MeshExecutor(network=InstantNetwork())
    res = mesh_ex.run("delta", w0, data, eval_data, tau=TAU)
    _assert_curves_match(res, oracle)
    np.testing.assert_allclose(np.asarray(res.w_shared),
                               np.asarray(oracle.w_shared),
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize(
    "m", [1, pytest.param(8, marks=pytest.mark.devices(8))])
def test_mesh_average_matches_oracle(m):
    data, eval_data, w0 = _setup(m)
    oracle = schemes.scheme_average(w0, data, eval_data, tau=TAU)
    res = MeshExecutor(network=InstantNetwork()).run(
        "average", w0, data, eval_data, tau=TAU)
    _assert_curves_match(res, oracle)


@pytest.mark.devices(8)
def test_mesh_async_matches_oracle_with_shared_delays():
    """Same NetworkModel draw => the mesh masked-merge protocol replays the
    eq.-(9) tick simulation exactly."""
    m = 8
    data, eval_data, w0 = _setup(m)
    key = jax.random.fold_in(KEY, 9)
    net = GeometricDelayNetwork(p_delay=0.5)
    sim = SimExecutor(network=net).run("async_delta", w0, data, eval_data,
                                       tau=TAU, key=key)
    res = MeshExecutor(network=net).run("async_delta", w0, data, eval_data,
                                        tau=TAU, key=key)
    _assert_curves_match(res, sim)
    np.testing.assert_allclose(np.asarray(res.w_shared),
                               np.asarray(sim.w_shared), rtol=1e-4, atol=1e-6)


@pytest.mark.devices(4)
@pytest.mark.parametrize("scheme, scopes", [
    ("delta", (mesh_lib.LOCAL_WINDOW_SCOPE, mesh_lib.MERGE_SCOPE,
               mesh_lib.EVAL_PROBE_SCOPE)),
    ("async_delta", (mesh_lib.EVAL_PROBE_SCOPE,)),
])
def test_compiled_segment_names_its_device_work(scheme, scopes):
    """The program's named scopes reach the compiled HLO's op_name
    metadata, which a profiler trace shows as each device op's ``tf_op``
    path: the eval probe, the local window and the merge are found by
    name, not by the fusion names XLA happens to give them."""
    data, eval_data, w0 = _setup(1)
    net = (InstantNetwork() if scheme == "delta"
           else GeometricDelayNetwork(p_delay=0.5))
    ex = MeshExecutor(network=net)
    programs = []
    run_compiled = ex._call_compiled

    def spy(cache_key, build, *args):
        programs.append((build(), args))
        return run_compiled(cache_key, build, *args)

    ex._call_compiled = spy
    ex.run(scheme, w0, data, eval_data, tau=TAU)
    (fn, args), = programs
    text = fn.lower(*args).compile().as_text()
    paths = [p.split("/") for p in re.findall(r'op_name="([^"]*)"', text)]
    for scope in scopes:
        assert any(scope in p for p in paths), scope


def test_mesh_pallas_and_reference_inner_loops_agree():
    data, eval_data, w0 = _setup(4)
    a = MeshExecutor(network=InstantNetwork(), use_pallas=True).run(
        "delta", w0, data, eval_data, tau=TAU)
    b = MeshExecutor(network=InstantNetwork(), use_pallas=False).run(
        "delta", w0, data, eval_data, tau=TAU)
    _assert_curves_match(a, b)


def test_sim_executor_is_the_oracle():
    data, eval_data, w0 = _setup(4)
    oracle = schemes.scheme_delta(w0, data, eval_data, tau=TAU)
    res = SimExecutor().run("delta", w0, data, eval_data, tau=TAU)
    np.testing.assert_array_equal(np.asarray(res.distortion),
                                  np.asarray(oracle.distortion))


def test_sim_async_lengths_roundtrip():
    """Passing a NetworkModel draw into scheme_async reproduces the default
    geometric sampling bit-for-bit (same key, same sampler)."""
    data, eval_data, w0 = _setup(4)
    key = jax.random.fold_in(KEY, 3)
    default = async_vq.scheme_async(w0, data, eval_data, key, tau=TAU,
                                    p_delay=0.5)
    m, n, _ = data.shape
    lengths = GeometricDelayNetwork(0.5).round_lengths(
        key, m, n // TAU + 2, TAU)
    explicit = async_vq.scheme_async(w0, data, eval_data, key, tau=TAU,
                                     p_delay=0.5, lengths=lengths)
    np.testing.assert_array_equal(np.asarray(default.distortion),
                                  np.asarray(explicit.distortion))


def test_thread_executor_smoke():
    data, eval_data, w0 = _setup(4, n=1000)
    ex = ThreadExecutor(duration_s=1.0)
    res = ex.run("async_delta", w0, data, eval_data, tau=TAU)
    assert float(res.distortion[-1]) < float(res.distortion[0])
    assert all(s.points > 0 for s in ex.last_stats)
    with pytest.raises(ValueError, match="async_delta"):
        ex.run("delta", w0, data, eval_data, tau=TAU)


# ---------------------------------------------------------------------------
# mesh / axis validation
# ---------------------------------------------------------------------------

@pytest.mark.devices(8)
def test_make_worker_mesh_validates():
    with pytest.raises(ValueError, match="non-empty"):
        make_worker_mesh(2, axis="")
    with pytest.raises(ValueError, match="devices"):
        make_worker_mesh(len(jax.devices()) + 1)
    mesh = make_worker_mesh(8)
    assert mesh.devices.shape == (8,)
    assert mesh.axis_names == ("workers",)


def test_mesh_executor_rejects_empty_axis_names():
    with pytest.raises(ValueError, match="non-empty"):
        MeshExecutor(axis="")
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("",))
    with pytest.raises(ValueError, match="non-empty"):
        MeshExecutor(mesh=mesh, axis="workers")


@pytest.mark.devices(2)
def test_mesh_executor_rejects_missing_axis():
    mesh = make_worker_mesh(2, axis="workers")
    with pytest.raises(ValueError, match="not in mesh axes"):
        MeshExecutor(mesh=mesh, axis="pods")


@pytest.mark.devices(4)
def test_mesh_executor_rejects_device_count_mismatch():
    data, eval_data, w0 = _setup(4)
    mesh = make_worker_mesh(2)  # 2 devices for 4 worker streams
    with pytest.raises(ValueError, match="one worker per device"):
        MeshExecutor(mesh=mesh).run("delta", w0, data, eval_data, tau=TAU)


def test_mesh_executor_rejects_bad_shapes():
    data, eval_data, w0 = _setup(2)
    ex = MeshExecutor()
    with pytest.raises(ValueError, match=r"\(M, n, d\)"):
        ex.run("delta", w0, data[0], eval_data, tau=TAU)
    with pytest.raises(ValueError, match="same M"):
        ex.run("delta", w0, data, eval_data[:1], tau=TAU)


# ---------------------------------------------------------------------------
# factories and pluggable pieces
# ---------------------------------------------------------------------------

def test_get_executor_factory():
    assert get_executor("sim").name == "sim"
    assert get_executor("mesh").name == "mesh"
    assert get_executor("thread").name == "thread"
    with pytest.raises(ValueError, match="unknown executor"):
        get_executor("quantum")
    with pytest.raises(ValueError, match="unknown scheme"):
        get_executor("sim").run("gossip", *(jnp.zeros((2, 2)),) * 1,
                                jnp.zeros((1, 4, 2)), jnp.zeros((1, 4, 2)),
                                tau=2)


def test_network_models():
    inst = get_network("instant")
    assert inst.window_ticks(10) == 10
    lengths = inst.round_lengths(KEY, 4, 5, 10)
    assert lengths.shape == (4, 5) and int(lengths.min()) == 10

    fixed = get_network("fixed", latency_ticks=3)
    assert fixed.window_ticks(10) == 13
    assert int(fixed.round_lengths(KEY, 2, 3, 10).max()) == 13

    geom = get_network("geometric", p_delay=0.5)
    g = geom.round_lengths(KEY, 16, 64, 10)
    assert int(g.min()) >= 10 and int(g.max()) > 10

    with pytest.raises(ValueError, match="unknown network"):
        get_network("wormhole")
    with pytest.raises(ValueError, match="p_delay"):
        GeometricDelayNetwork(p_delay=0.0)


@pytest.mark.devices(4)
def test_fixed_latency_network_stretches_wall_clock():
    """Same merges, same curve VALUES — but each window costs more ticks, so
    convergence in wall time is slower (the paper's communication tax)."""
    data, eval_data, w0 = _setup(4)
    free = MeshExecutor(network=InstantNetwork()).run(
        "delta", w0, data, eval_data, tau=TAU)
    taxed = MeshExecutor(network=get_network("fixed", latency_ticks=5)).run(
        "delta", w0, data, eval_data, tau=TAU)
    np.testing.assert_allclose(np.asarray(free.distortion),
                               np.asarray(taxed.distortion), rtol=1e-6)
    assert int(taxed.wall_ticks[0]) == TAU + 5
    assert int(taxed.wall_ticks[-1]) > int(free.wall_ticks[-1])


def test_executor_protocol_runtime_checkable():
    assert isinstance(SimExecutor(), engine.Executor)
    assert isinstance(MeshExecutor(), engine.Executor)
    assert isinstance(ThreadExecutor(), engine.Executor)
