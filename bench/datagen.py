"""The benchmark's own inputs, made on the device from ``--seed``.

A copy of the program's synthetic stream (an isotropic Gaussian mixture
around uniform random centers in [0, 1]^d) and of its codebook
initialisation (sample kappa distinct points of the stream), kept here so
that a change to the program cannot move the data a cell is measured on.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key for ``seed`` (any non-negative int, also past 32 bits),
    folded with ``stream`` so independent inputs of one run differ."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    k = jax.random.fold_in(k, seed >> 32)
    return jax.random.fold_in(k, stream)


@functools.partial(jax.jit, static_argnames=("n", "d", "n_centers", "noise"))
def mixture(k: jax.Array, *, n: int, d: int, n_centers: int,
            noise: float) -> jax.Array:
    """(n, d) f32 points of a uniform-center isotropic Gaussian mixture."""
    kc, ka, kn = jax.random.split(k, 3)
    centers = jax.random.uniform(kc, (n_centers, d), dtype=jnp.float32)
    assign = jax.random.randint(ka, (n,), 0, n_centers)
    eps = noise * jax.random.normal(kn, (n, d), dtype=jnp.float32)
    return centers[assign] + eps


@functools.partial(jax.jit, static_argnames=("kappa",))
def sample_rows(k: jax.Array, data: jax.Array, kappa: int) -> jax.Array:
    """kappa distinct rows of ``data``: the codebook initialisation."""
    idx = jax.random.choice(k, data.shape[0], (kappa,), replace=False)
    return data[idx]


def permuted(values: np.ndarray, seed: int, stream: int) -> np.ndarray:
    """``values`` in an order drawn from ``seed``: every seed gets the same
    multiset (the same work), in another order."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, stream])
    return values[rng.permutation(len(values))]
