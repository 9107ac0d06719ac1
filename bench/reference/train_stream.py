"""Plain reference of the paper's delta scheme, independent of the program.

Each window, every worker runs ``tau`` sequential VQ steps (eq. 1) from
the shared codebook on its own points; the shared codebook then moves by
the sum of the workers' displacements (eqs. 7-8).  The step size is
``eps0 / (1 + decay * t)`` at the worker's local step ``t``.  The nearest
code is found from exact differences, ``sum((w - z)**2)``, so no matrix
product and no matmul precision is involved; ties go to the lowest index.

``dtype=jnp.bfloat16`` is the control: the same arithmetic with storage
and products in bfloat16, the precision below the configuration's.
``fault`` plants one of the faults the check has to catch, for reading
what it does to the compared numbers: ``"half"`` leaves out every other
point of each window, ``"no_merge"`` leaves out the exchange between
workers (the shared codebook takes worker 0's result alone).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

FAULTS = (None, "half", "no_merge")


@functools.partial(jax.jit,
                   static_argnames=("tau", "eps0", "decay", "dtype", "fault"))
def run_chunk(w: jax.Array, t0: jax.Array, chunk: jax.Array, *, tau: int,
              eps0: float, decay: float, dtype=jnp.float32,
              fault: str | None = None) -> jax.Array:
    """Shared codebook after the windows of ``chunk`` (M, n, d), starting
    at local step ``t0``."""
    m, n, d = chunk.shape
    nw = n // tau
    wins = chunk[:, :nw * tau].reshape(m, nw, tau, d).transpose(1, 0, 2, 3)
    stride = 2 if fault == "half" else 1

    def worker(w, zs, t):
        def step(j, w):
            i = j * stride
            z = zs[i]
            l = jnp.argmin(jnp.sum((w - z) ** 2, axis=1))
            eps = eps0 / (1.0 + decay * (t + 1 + i).astype(jnp.float32))
            row = w[l]
            return w.at[l].set(row - eps.astype(dtype) * (row - z))

        return jax.lax.fori_loop(0, tau // stride, step, w)

    def window(carry, zwin):
        w, t = carry
        local = jax.vmap(worker, in_axes=(None, 0, None))(w, zwin, t)
        if fault == "no_merge":
            return (local[0], t + tau), None
        return (w - jnp.sum(w[None] - local, axis=0), t + tau), None

    (w, _), _ = jax.lax.scan(window, (w.astype(dtype), t0),
                             wins.astype(dtype))
    return w


@functools.partial(jax.jit, static_argnames=("dtype",))
def distortion(w: jax.Array, ev: jax.Array, dtype=jnp.float32) -> jax.Array:
    """Mean over all held-out points ``ev`` (M, n_eval, d) of the squared
    distance to the nearest code of ``w``.

    Distances use the expansion ``|z|^2 - 2 z.w + |w|^2`` with the product
    at ``Precision.HIGHEST`` (float32 exact to rounding), in blocks of 512
    points."""
    d = ev.shape[-1]
    w = w.astype(dtype)
    w2 = jnp.sum(w * w, axis=1)
    prec = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)

    def block(z):
        z = z.astype(dtype)
        d2 = (jnp.sum(z * z, axis=1, keepdims=True)
              - 2 * jnp.dot(z, w.T, precision=prec) + w2[None])
        return jnp.min(d2, axis=1).astype(jnp.float32)

    mins = jax.lax.map(block, ev.reshape(-1, 512, d))
    return jnp.mean(mins)


def follow(w0: jax.Array, chunks: list, ev: jax.Array, *, tau: int,
           eps0: float, decay: float, dtype=jnp.float32,
           fault: str | None = None) -> tuple[list, list[float]]:
    """Follow the program through ``chunks``: the codebook after each, and
    the held-out distortion there."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; choose from {FAULTS}")
    ws, losses = [], []
    w, t = w0, 0
    for chunk in chunks:
        w = run_chunk(w, jnp.asarray(t, jnp.int32), chunk, tau=tau,
                      eps0=eps0, decay=decay, dtype=dtype, fault=fault)
        t += chunk.shape[1] // tau * tau
        ws.append(w.astype(jnp.float32))
        losses.append(float(distortion(w, ev, dtype=dtype)))
    return ws, losses
