"""Product quantization on the engine.

A (m, k, d/m) codebook is m sub-codebooks; each point's m sub-vectors
train them as m independent eq.-1 VQs with one step size.  The engine
runs them through the ``pq_window`` kernel (interpret mode here) or a
scan of ``vq.pq_H``; both are checked against a plain float32 reference
at m=4, k=16, d=32, tau=10.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import vq
from repro.engine import MeshExecutor
from repro.kernels import ops
from repro.topology import make_worker_mesh

M_SUB, K, D, TAU = 4, 16, 32, 10
EPS0, DECAY = 0.5, 1.0
# The program and the reference sum each sub-distance's 8 squares, and the
# merges their M displacements, in orders of their own: float32 results a
# few ulps apart.  The smallest gap between a sub-vector's nearest and
# second-nearest code here is some 4e-5 on distances of about 1, hundreds
# of ulps, so no argmin flips and the sub-codebooks agree to rounding;
# 1e-5 on unit-scale entries leaves room for the rounding carried through
# 40 steps.
TOL = 1e-5


def _reference(w0, data, ev, scheme):
    """Plain numpy float32: every window, each worker takes TAU eq.-1 steps
    in every sub-space from the shared sub-codebooks (nearest code from
    exact differences, ties to the lowest index), then the workers'
    results merge (eq. 8's summed displacements, or eq. 3's mean).
    Returns the sub-codebooks and the PQ distortion after each window."""
    w = np.asarray(w0, np.float32).copy()
    data = np.asarray(data, np.float32)
    ev = np.asarray(ev, np.float32)
    workers, n, _ = data.shape
    m, _, ds = w.shape
    curve = []
    for win in range(n // TAU):
        local = []
        for i in range(workers):
            wl = w.copy()
            for s in range(TAU):
                t = win * TAU + s
                eps = np.float32(EPS0) / (
                    np.float32(1.0) + np.float32(DECAY) * np.float32(t + 1))
                h = wl - data[i, t].reshape(m, 1, ds)
                near = np.argmin(np.sum(h * h, axis=-1), axis=-1)
                for j, l in enumerate(near):
                    wl[j, l] -= eps * h[j, l]
            local.append(wl)
        local = np.stack(local)
        if scheme == "delta":
            w = w - np.sum(w[None] - local, axis=0)
        else:
            w = np.mean(local, axis=0)
        zs = ev.reshape(-1, m, 1, ds)
        curve.append(np.mean(np.sum(np.min(
            np.sum((zs - w[None]) ** 2, axis=-1), axis=-1), axis=-1)))
    return w, np.array(curve)


def _problem(workers, n=4 * TAU, seed=0):
    rng = np.random.default_rng(seed)
    data = rng.random((workers, n, D), dtype=np.float32)
    ev = rng.random((workers, 64, D), dtype=np.float32)
    w0 = rng.random((M_SUB, K, D // M_SUB), dtype=np.float32)
    return w0, data, ev


@pytest.mark.parametrize("use_pallas", [True, False],
                         ids=["pq_window", "scan"])
@pytest.mark.parametrize("scheme", ["delta", "average"])
@pytest.mark.parametrize(
    "workers", [1, pytest.param(4, marks=pytest.mark.devices(4))])
def test_engine_pq_matches_reference(use_pallas, scheme, workers):
    w0, data, ev = _problem(workers)
    ex = MeshExecutor(mesh=make_worker_mesh(workers, "workers"),
                      use_pallas=use_pallas)
    res = ex.run(scheme, jnp.asarray(w0), jnp.asarray(data), jnp.asarray(ev),
                 tau=TAU, eps0=EPS0, decay=DECAY)
    want_w, want_curve = _reference(w0, data, ev, scheme)
    assert res.w_shared.shape == (M_SUB, K, D // M_SUB)
    np.testing.assert_allclose(np.asarray(res.w_shared), want_w, rtol=0,
                               atol=TOL)
    # the probe's sub-distances use the matmul expansion, exact to float32
    # rounding on the CPU: relative rounding of sums of unit-scale terms
    np.testing.assert_allclose(np.asarray(res.distortion), want_curve,
                               rtol=TOL)
    assert not np.allclose(np.asarray(res.w_shared), w0)


def test_segments_continue_the_step_schedule():
    """Two segments from t0 = 0 and t0 = 20 are the one run of 40 points."""
    w0, data, ev = _problem(1)
    ex = MeshExecutor(mesh=make_worker_mesh(1, "workers"))
    kw = dict(tau=TAU, eps0=EPS0, decay=DECAY)
    whole = ex.run_segment("delta", jnp.asarray(w0), jnp.asarray(data),
                           jnp.asarray(ev), **kw)
    half = ex.run_segment("delta", jnp.asarray(w0), jnp.asarray(data[:, :20]),
                          jnp.asarray(ev), **kw)
    rest = ex.run_segment("delta", half.w_shared, jnp.asarray(data[:, 20:]),
                          jnp.asarray(ev), t0=20, **kw)
    np.testing.assert_array_equal(np.asarray(rest.w_shared),
                                  np.asarray(whole.w_shared))


def _pq_scan(zwin, w0, eps):
    def body(w, x):
        return w - x[1] * vq.pq_H(x[0], w), None

    return jax.lax.scan(body, w0, (zwin, eps))[0]


@pytest.mark.parametrize("m, k, ds, tau", [(4, 16, 8, 10), (16, 256, 8, 10),
                                           (8, 32, 4, 7)])
def test_pq_window_matches_scan(m, k, ds, tau):
    """Interpret mode runs the kernel's float32 ops on the CPU: the same
    differences, squares and updates as the scan, so equal bit for bit."""
    rng = np.random.default_rng(m * k + tau)
    zwin = jnp.asarray(rng.random((tau, m * ds), dtype=np.float32))
    w0 = jnp.asarray(rng.random((m, k, ds), dtype=np.float32))
    eps = vq.default_steps(1 + jnp.arange(tau), eps0=EPS0, decay=DECAY)
    got = jax.jit(ops.pq_window)(zwin, w0, eps)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jax.jit(_pq_scan)(zwin, w0, eps)))


def test_pq_window_ties_go_to_the_lowest_code():
    """Codes 3 and 9 are the same in every sub-space and nearest to every
    point: code 3 takes every step, code 9 never moves."""
    m, k, ds, tau = 4, 16, 8, 10
    rng = np.random.default_rng(5)
    w0 = rng.random((m, k, ds), dtype=np.float32) + 10.0
    w0[:, 9] = w0[:, 3] = 0.5
    zwin = rng.random((tau, m * ds), dtype=np.float32) * 0.01 + 0.5
    eps = vq.default_steps(1 + jnp.arange(tau), eps0=EPS0, decay=DECAY)
    got = np.asarray(ops.pq_window(jnp.asarray(zwin), jnp.asarray(w0), eps))
    want = np.asarray(_pq_scan(jnp.asarray(zwin), jnp.asarray(w0), eps))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got[:, 9], w0[:, 9])
    assert not np.array_equal(got[:, 3], w0[:, 3])
    np.testing.assert_array_equal(np.delete(got, [3], axis=1),
                                  np.delete(w0, [3], axis=1))


@pytest.mark.parametrize("scheme, kw", [
    ("async_delta", {}),
    ("delta", {"transport": "sparse"}),
    ("delta", {"merge": "quorum"}),
], ids=["async", "sparse-transport", "quorum-merge"])
def test_pq_codebook_outside_the_sync_xla_path_raises(scheme, kw):
    w0, data, ev = _problem(1)
    ex = MeshExecutor(mesh=make_worker_mesh(1, "workers"), **kw)
    with pytest.raises(ValueError, match="product quantizer"):
        ex.run(scheme, jnp.asarray(w0), jnp.asarray(data), jnp.asarray(ev),
               tau=TAU)


def test_pq_codebook_must_split_d():
    w0, data, ev = _problem(1)
    ex = MeshExecutor(mesh=make_worker_mesh(1, "workers"))
    with pytest.raises(ValueError, match="sub-codebooks"):
        ex.run_segment("delta", jnp.asarray(w0[:, :, :4]), jnp.asarray(data),
                       jnp.asarray(ev), tau=TAU)


def test_profiler_notes_codes_per_sub_codebook():
    from repro.obs.profile import Profiler

    w0, data, ev = _problem(1)
    prof = Profiler()
    notes = []
    orig = prof.note_segment
    prof.note_segment = lambda **kw: (notes.append(kw), orig(**kw))
    ex = MeshExecutor(mesh=make_worker_mesh(1, "workers"), profiler=prof)
    ex.run("delta", jnp.asarray(w0), jnp.asarray(data), jnp.asarray(ev),
           tau=TAU)
    assert notes[0]["kappa"] == K and notes[0]["subspaces"] == M_SUB
    assert notes[0]["d"] == D
