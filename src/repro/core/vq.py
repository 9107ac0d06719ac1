"""Sequential stochastic Vector Quantization (online k-means) — paper eqs. (1), (2), (4), (5).

The paper's objects, verbatim in JAX:

  * ``H(z, w)``  (eq. 4): the one-prototype displacement direction,
    ``H(z,w)_l = (w_l - z) * 1{l = argmin_i ||z - w_i||^2}``.
  * the sequential VQ iteration (eq. 1): ``w <- w - eps_{t+1} H(z_{t+1}, w)``.
  * the distortion criterion (eq. 2):
    ``C_{n,M}(w) = 1/(nM) sum_{i,t} min_l ||z_t^i - w_l||^2``.

Everything is pure-functional and jit/scan/vmap friendly.  ``H`` is written
with the matmul expansion ``||z-w||^2 = ||z||^2 - 2 z.w + ||w||^2`` so the
hot path hits the MXU on TPU; the Pallas kernel in ``repro.kernels`` is the
blocked version of the same computation for large (batch, kappa, d).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp


class VQState(NamedTuple):
    """Carried state of a sequential VQ run."""

    w: jax.Array  # (kappa, d) prototypes
    t: jax.Array  # scalar int32 step counter (drives the step schedule)


def squared_distances(z: jax.Array, w: jax.Array) -> jax.Array:
    """Pairwise squared distances ``(batch, kappa)`` via the matmul expansion.

    z: (batch, d), w: (kappa, d).  Uses ||z||^2 - 2 z.w^T + ||w||^2 which is
    MXU-friendly (one (batch,d)x(d,kappa) matmul) rather than the O(batch *
    kappa * d) broadcast-subtract which is VPU-bound and 3x the HBM traffic.
    """
    z2 = jnp.sum(z * z, axis=-1, keepdims=True)  # (batch, 1)
    w2 = jnp.sum(w * w, axis=-1)  # (kappa,)
    cross = z @ w.T  # (batch, kappa)
    return z2 - 2.0 * cross + w2[None, :]


def nearest(z: jax.Array, w: jax.Array) -> jax.Array:
    """argmin_l ||z - w_l||^2, per row of ``z``.  Shape (batch,)."""
    return jnp.argmin(squared_distances(z, w), axis=-1)


def H(z: jax.Array, w: jax.Array) -> jax.Array:
    """Paper eq. (4) for a single sample.

    z: (d,), w: (kappa, d) -> (kappa, d), nonzero only on the winning row.
    """
    l = nearest(z[None, :], w)[0]
    onehot = jax.nn.one_hot(l, w.shape[0], dtype=w.dtype)  # (kappa,)
    return onehot[:, None] * (w - z[None, :])


def H_batch(z: jax.Array, w: jax.Array) -> jax.Array:
    """Sum of H(z_b, w) over a minibatch — the mini-batch displacement.

    z: (batch, d), w: (kappa, d) -> (kappa, d).  Equivalent to
    ``sum_b H(z[b], w)`` but computed as a one-hot matmul (MXU-friendly).
    """
    l = nearest(z, w)  # (batch,)
    onehot = jax.nn.one_hot(l, w.shape[0], dtype=w.dtype)  # (batch, kappa)
    counts = jnp.sum(onehot, axis=0)  # (kappa,)
    zsum = onehot.T @ z  # (kappa, d)
    return counts[:, None] * w - zsum


def distortion(z: jax.Array, w: jax.Array) -> jax.Array:
    """Paper eq. (2) for one worker's data: mean_t min_l ||z_t - w_l||^2."""
    return jnp.mean(jnp.min(squared_distances(z, w), axis=-1))


def distortion_multi(z: jax.Array, w: jax.Array) -> jax.Array:
    """Eq. (2) over M workers: z is (M, n, d); normalizes by n*M."""
    return jnp.mean(jax.vmap(lambda zi: distortion(zi, w))(z))


def pq_H(z: jax.Array, w: jax.Array) -> jax.Array:
    """Eq. (4) in every sub-space of a product quantizer.

    z: (d,), w: (m, k, d/m) sub-codebooks -> (m, k, d/m): in sub-space j,
    ``w_j[l] - z_j`` on the row ``l`` nearest to the sub-vector ``z_j``,
    zero elsewhere.  The sub-distances are exact float32 differences, not
    the matmul expansion: at d/m = 8 the product would gain nothing and,
    at the TPU's default one-bf16-pass precision, would flip argmins."""
    m, k, ds = w.shape
    h = w - z.reshape(m, 1, ds)
    l = jnp.argmin(jnp.sum(h * h, axis=-1), axis=-1)  # (m,)
    return jax.nn.one_hot(l, k, dtype=w.dtype)[..., None] * h


def pq_distortion(z: jax.Array, w: jax.Array) -> jax.Array:
    """Eq. (2) for a product quantizer: mean over the points ``z`` (n, d)
    of the sum over sub-spaces of each sub-vector's squared distance to
    its nearest code in ``w`` (m, k, d/m)."""
    m, _, ds = w.shape
    zs = jnp.swapaxes(z.reshape(-1, m, ds), 0, 1)  # (m, n, d/m)
    mins = jax.vmap(lambda zj, wj: jnp.min(squared_distances(zj, wj),
                                           axis=-1))(zs, w)
    return jnp.mean(jnp.sum(mins, axis=0))


def default_steps(t: jax.Array, *, eps0: float = 0.5, decay: float = 1.0) -> jax.Array:
    """The classical Robbins-Monro schedule eps_t = eps0 / (1 + decay * t).

    The paper assumes "a satisfactory sequential implementation", i.e. a
    step sequence adapted to the dataset; this is the standard choice used
    in [1] (Patra, JMLR 2011) and keeps sum eps_t = inf, sum eps_t^2 < inf.
    """
    return eps0 / (1.0 + decay * t.astype(jnp.float32))


def vq_step(state: VQState, z: jax.Array, *, eps0: float = 0.5, decay: float = 1.0) -> VQState:
    """One sequential VQ iteration — paper eq. (1)."""
    eps = default_steps(state.t + 1, eps0=eps0, decay=decay)
    w = state.w - eps * H(z, state.w)
    return VQState(w=w, t=state.t + 1)


@functools.partial(jax.jit, static_argnames=("eps0", "decay"))
def vq_run(w0: jax.Array, data: jax.Array, *, t0: int | jax.Array = 0,
           eps0: float = 0.5, decay: float = 1.0) -> VQState:
    """Run sequential VQ over ``data`` (n, d) in order — eq. (5) unrolled by scan."""

    def body(state: VQState, z: jax.Array) -> tuple[VQState, None]:
        return vq_step(state, z, eps0=eps0, decay=decay), None

    init = VQState(w=w0, t=jnp.asarray(t0, jnp.int32))
    final, _ = jax.lax.scan(body, init, data)
    return final


def window_displacement(w0: jax.Array, data: jax.Array, t0: jax.Array,
                        *, eps0: float = 0.5, decay: float = 1.0) -> tuple[jax.Array, jax.Array]:
    """Delta_{t0 -> t0+tau}: the accumulated displacement of tau sequential VQ
    steps starting from prototypes ``w0`` at global step ``t0`` (paper eq. 7).

    Returns (delta, w_final) with ``w_final = w0 - delta``.
    """
    final = vq_run(w0, data, t0=t0, eps0=eps0, decay=decay)
    return w0 - final.w, final.w
