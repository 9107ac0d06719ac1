"""The engine's per-step route (``mesh._step_window``): one ``vq_assign``
dispatch a step against carried row norms, and only the winning row and
its norm updated.  It is the route of codebooks past the window kernel's
VMEM budget; on XLA:CPU it is pinned bitwise to the sequential oracle
(``vq.vq_run``) and to the window kernel.  Also pinned here: which Pallas
kernels each of the engine's routes traces."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore
from jax.sharding import Mesh

from repro.core import vq
from repro.engine import InstantNetwork, MeshExecutor
from repro.engine import mesh as mesh_lib
from repro.kernels import ops

KEY = jax.random.PRNGKey(17)
TAU = 10


def _case(kappa, d, n, ties):
    """n points and a (kappa, d) codebook; with ``ties`` every code is
    there twice, so each step's nearest code is a tie the lower index
    must win."""
    kz, kw = jax.random.split(jax.random.fold_in(KEY, kappa))
    z = jax.random.normal(kz, (n, d))
    if ties:
        half = jax.random.normal(kw, (kappa // 2, d))
        return z, jnp.concatenate([half, half])
    return z, jax.random.normal(kw, (kappa, d))


def _step_budget(kappa, d):
    """A budget the window kernel misses and a one-block assign fits."""
    budget = ops.window_vmem_bytes(kappa, d, TAU) - 1
    assert ops.assign_vmem_bytes(kappa, d, bm=8, bk=kappa) <= budget
    return budget


def _windows(w0, z, *, budget):
    f = jax.jit(lambda w, zw, t: mesh_lib._local_window(
        w, zw, t, eps0=0.5, decay=1.0, use_pallas=True,
        vmem_budget=budget)[1])
    w = w0
    for i in range(z.shape[0] // TAU):
        w = f(w, z[i * TAU:(i + 1) * TAU], jnp.int32(i * TAU))
    return w


def _kernels(closed):
    """(name, grid) of every Pallas call in a traced program."""
    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield e.params["name"], e.params["grid_mapping"].grid
                continue
            for v in e.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    if isinstance(sub, jcore.ClosedJaxpr):
                        yield from walk(sub.jaxpr)
                    elif isinstance(sub, jcore.Jaxpr):
                        yield from walk(sub)
    return list(walk(closed.jaxpr))


def _step_kernels(kappa, d, budget):
    return _kernels(jax.make_jaxpr(lambda w, z, t: mesh_lib._local_window(
        w, z, t, eps0=0.5, decay=1.0, use_pallas=True,
        vmem_budget=budget))(jnp.zeros((kappa, d)), jnp.zeros((TAU, d)),
                             jnp.int32(0)))


@pytest.mark.parametrize("kappa, d, ties", [(16, 8, False), (64, 32, True),
                                            (1024, 128, False)])
def test_step_route_bitwise_matches_oracle_and_window_kernel(kappa, d, ties):
    z, w0 = _case(kappa, d, 3 * TAU, ties)
    budget = _step_budget(kappa, d)
    assert not ops.window_fits_vmem(kappa, d, TAU, budget_bytes=budget)
    assert ops.window_fits_vmem(kappa, d, TAU)
    w_step = _windows(w0, z, budget=budget)
    w_oracle = vq.vq_run(w0, z).w
    w_window = _windows(w0, z, budget=None)
    # the same float operations on every route: BITWISE, not allclose
    assert np.array_equal(np.asarray(w_step), np.asarray(w_oracle))
    assert np.array_equal(np.asarray(w_step), np.asarray(w_window))


@pytest.mark.parametrize("ties", [False, True])
def test_blocked_step_route_bitwise_matches_oracle(ties):
    """Past a one-block fit the tuner's blocked grid runs, kappa padded to
    a whole number of blocks; the carried norms are padded with it."""
    kappa, d, budget = 600, 16, 40_000
    assert ops.assign_vmem_bytes(kappa, d, bm=8, bk=kappa) > budget
    ((name, grid),) = set(_step_kernels(kappa, d, budget))
    assert name == "vq_assign" and grid[1] > 1
    z, w0 = _case(kappa, d, 3 * TAU, ties)
    w_step = _windows(w0, z, budget=budget)
    assert np.array_equal(np.asarray(w_step),
                          np.asarray(vq.vq_run(w0, z).w))


def test_carried_norms_equal_recomputed_after_every_window():
    kappa, d = 64, 32
    z, w0 = _case(kappa, d, 4 * TAU, False)
    f = jax.jit(lambda w, zw, t: mesh_lib._step_window(
        w, zw, t, eps0=0.5, decay=1.0, vmem_budget=_step_budget(kappa, d)))
    # compiled, as on the route's entry: XLA:CPU rounds an eager
    # multiply-then-reduce differently from the fused one
    norms = jax.jit(lambda w: jnp.sum(w * w, axis=1)[None, :])
    w = w0
    for i in range(4):
        w, w2 = f(w, z[i * TAU:(i + 1) * TAU], jnp.int32(i * TAU))
        assert w2.shape == (1, kappa)
        assert np.array_equal(np.asarray(w2), np.asarray(norms(w)))


class _Traced(Exception):
    pass


def _program_kernels(scheme, w0, *, budget=None):
    """(name, grid) of every Pallas call in the program the executor
    builds for one run of ``scheme`` on a one-device mesh (traced only)."""
    d = w0.shape[0] * w0.shape[2] if w0.ndim == 3 else w0.shape[1]
    ex = MeshExecutor(mesh=Mesh(np.array(jax.devices()[:1]), ("workers",)),
                      network=InstantNetwork(), vmem_budget_bytes=budget)
    kernels = []

    def trace_only(cache_key, build, *args):
        kernels.extend(_kernels(jax.make_jaxpr(build())(*args)))
        raise _Traced

    ex._call_compiled = trace_only
    with pytest.raises(_Traced):
        ex.run(scheme, w0, jnp.zeros((1, 2 * TAU, d)), jnp.zeros((1, 8, d)),
               tau=TAU)
    return kernels


@pytest.mark.parametrize("route", ["window", "per_step", "pq",
                                   "async_in_budget", "async_past_budget"])
def test_step_program_runs_assign_not_delta(route):
    """Each engine route traces its one kernel: the window kernel while a
    window fits the VMEM budget; past it one ``vq_assign`` call a step,
    the whole codebook as one block (a grid of one), and no ``vq_delta``;
    the PQ window for sub-codebooks; and on the async tick the
    full-codebook delta kernel, or the blocked one past its budget."""
    kappa, d = 1024, 128
    w0 = jnp.zeros((kappa, d))
    delta_budget = ops.delta_vmem_bytes(kappa, d, bm=8)
    scheme, w0, budget, want = {
        "window": ("delta", w0, None, [("vq_window", (1,))]),
        "per_step": ("delta", w0, _step_budget(kappa, d),
                     [("vq_assign", (1, 1))]),
        "pq": ("delta", jnp.zeros((16, 256, 8)), None, [("pq_window", (1,))]),
        "async_in_budget": ("async_delta", w0, delta_budget,
                            [("vq_delta", (1,))]),
        "async_past_budget": ("async_delta", w0, delta_budget - 1,
                              [("vq_delta_blocked", None)]),
    }[route]
    kernels = _program_kernels(scheme, w0, budget=budget)
    assert [name for name, _ in kernels] == [name for name, _ in want]
    for (_, grid), (_, want_grid) in zip(kernels, want):
        assert want_grid is None or grid == want_grid


def test_assign_with_kept_norms_matches_plain_assign():
    z, w = _case(40, 8, 24, True)
    w2 = jnp.sum(w * w, axis=1)[None, :]
    for bk in (8, 40):
        a, m = ops.vq_assign(z, w, w2=w2, bm=8, bk=bk)
        a_ref, m_ref = ops.vq_assign(z, w, bm=8, bk=bk)
        assert np.array_equal(np.asarray(a), np.asarray(a_ref))
        assert np.array_equal(np.asarray(m), np.asarray(m_ref))
