"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # image ships without hypothesis: deterministic shim
    from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)

SWEEP = [
    (8, 8, 8), (64, 16, 4), (128, 128, 32), (256, 300, 64),
    (100, 17, 5), (512, 64, 128), (33, 129, 7),
]


@pytest.mark.parametrize("batch,kappa,d", SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_vq_assign_matches_ref(batch, kappa, d, dtype):
    kz, kw = jax.random.split(jax.random.fold_in(KEY, batch * kappa + d))
    z = jax.random.normal(kz, (batch, d), dtype)
    w = jax.random.normal(kw, (kappa, d), dtype)
    a, m = ops.vq_assign(z, w)
    ar, mr = ref.vq_assign_ref(z, w)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    # ties under bf16 rounding can flip the argmin: check distances instead
    np.testing.assert_allclose(np.asarray(m), np.asarray(mr),
                               rtol=tol, atol=tol)
    if dtype == jnp.float32:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(ar))


@pytest.mark.parametrize("batch,kappa,d", SWEEP)
def test_vq_delta_matches_ref(batch, kappa, d):
    kz, kw = jax.random.split(jax.random.fold_in(KEY, batch + kappa * d))
    z = jax.random.normal(kz, (batch, d))
    w = jax.random.normal(kw, (kappa, d))
    c, s = ops.vq_delta(z, w)
    cr, sr = ref.vq_delta_ref(z, w)
    np.testing.assert_allclose(np.asarray(c), np.asarray(cr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("batch,kappa,d", SWEEP[:4])
def test_distortion_matches_ref(batch, kappa, d):
    kz, kw = jax.random.split(jax.random.fold_in(KEY, batch))
    z = jax.random.normal(kz, (batch, d))
    w = jax.random.normal(kw, (kappa, d))
    np.testing.assert_allclose(float(ops.distortion(z, w)),
                               float(ref.distortion_ref(z, w)), rtol=1e-4)


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 200), st.integers(2, 100), st.integers(1, 48),
       st.integers(0, 2**31 - 1))
def test_vq_delta_properties(batch, kappa, d, seed):
    """Invariants: counts sum to batch; zsum column sums == data column sums;
    delta == counts*w - zsum reproduces H_batch."""
    key = jax.random.PRNGKey(seed)
    kz, kw = jax.random.split(key)
    z = jax.random.normal(kz, (batch, d))
    w = jax.random.normal(kw, (kappa, d))
    c, s = ops.vq_delta(z, w)
    assert float(jnp.sum(c)) == pytest.approx(batch, abs=1e-3)
    np.testing.assert_allclose(np.asarray(jnp.sum(s, axis=0)),
                               np.asarray(jnp.sum(z, axis=0)),
                               rtol=1e-3, atol=1e-3)
    from repro.core import vq as vq_core
    delta = c[:, None] * w - s
    np.testing.assert_allclose(np.asarray(delta),
                               np.asarray(vq_core.H_batch(z, w)),
                               rtol=1e-3, atol=1e-3)


def test_block_size_invariance():
    """Same results regardless of BlockSpec tile sizes."""
    z = jax.random.normal(KEY, (512, 24))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (200, 24))
    a1, m1 = ops.vq_assign(z, w, bm=128, bk=128)
    a2, m2 = ops.vq_assign(z, w, bm=64, bk=32)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), rtol=1e-5)


def test_vmem_budget_routing():
    """The routing helper: explicit > env var > default; residency math."""
    assert ops.vmem_budget_bytes() == ops.DEFAULT_VMEM_BUDGET_BYTES
    assert ops.vmem_budget_bytes(1234) == 1234
    with pytest.raises(ValueError):
        ops.vmem_budget_bytes(0)
    # a 16x8 codebook trivially fits; a huge one cannot
    assert ops.delta_fits_vmem(16, 8)
    assert not ops.delta_fits_vmem(1 << 20, 512)
    assert ops.codebook_fits_vmem(16, 8)
    assert not ops.codebook_fits_vmem(16, 8, budget_bytes=64)
    # the fused kernel's residency grows with kappa*d
    assert (ops.delta_vmem_bytes(1024, 64)
            > ops.delta_vmem_bytes(128, 64))


def test_window_route_at_the_training_cells_widths():
    """The window router as the training cells see it at d=128, tau=10:
    kappa=1,024 takes the fused window kernel, kappa=4,096 the per-step
    route, under the default 8 MiB budget."""
    assert ops.DEFAULT_VMEM_BUDGET_BYTES == 8 * 1024 * 1024
    assert ops.window_fits_vmem(1024, 128, 10)
    assert not ops.window_fits_vmem(4096, 128, 10)


@pytest.mark.parametrize("batch,kappa,d", [(100, 200, 16), (64, 300, 8)])
def test_vq_delta_routed_blocked_parity_kappa_gt_bk(batch, kappa, d):
    """kappa > bk forces the blocked-assign + segment-sum fallback; it must
    reproduce the fused kernel / oracle exactly (first step of the
    larger-than-VMEM-codebooks roadmap item, scoped to the lookup path)."""
    kz, kw = jax.random.split(jax.random.fold_in(KEY, batch * kappa))
    z = jax.random.normal(kz, (batch, d))
    w = jax.random.normal(kw, (kappa, d))
    assert kappa > 128  # the bk block size: the codebook IS streamed
    # tiny budget -> blocked path; default budget -> fused path
    c_blk, s_blk = ops.vq_delta_routed(z, w, bk=128, budget_bytes=1024)
    c_fus, s_fus = ops.vq_delta_routed(z, w)
    assert not ops.delta_fits_vmem(kappa, d, budget_bytes=1024)
    assert ops.delta_fits_vmem(kappa, d)
    cr, sr = ref.vq_delta_ref(z, w)
    for c, s in ((c_blk, s_blk), (c_fus, s_fus)):
        np.testing.assert_allclose(np.asarray(c), np.asarray(cr), atol=1e-5)
        np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                                   rtol=1e-4, atol=1e-4)


def test_minibatch_step_reduces_distortion():
    from repro.data import synthetic
    data = synthetic.mixture_data(KEY, n=4096, d=16, n_centers=8)
    w = synthetic.kmeanspp_init(jax.random.fold_in(KEY, 3), data, 32)
    d0 = float(ref.distortion_ref(data, w))
    for i in range(10):
        w = ops.vq_minibatch_step(data[i * 256:(i + 1) * 256], w,
                                  jnp.asarray(0.5))
    d1 = float(ref.distortion_ref(data, w))
    assert d1 < d0
