"""Plain reference of the delta scheme on a product quantizer.

A product quantizer's codebook is m sub-codebooks (m, k, d/m); point z is
split into m sub-vectors of d/m coordinates, and sub-codebook j learns
from sub-vector j alone.  So each sub-space is an independent eq.-1 VQ
with the same points and step sizes, and the delta merge, a sum over
workers of each entry's displacement, commutes with the split.  This is
``train_stream.run_chunk`` vmapped over the m sub-spaces, and the
distortion is the sum over sub-spaces of ``train_stream.distortion``:
exact differences in the steps, ``Precision.HIGHEST`` products in the
distortion, float32.

``dtype=jnp.bfloat16`` is the control and ``fault`` a planted fault, as
in ``train_stream``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference import train_stream as vq

FAULTS = (None, "half")


def split(x: jax.Array, m: int) -> jax.Array:
    """(..., d) -> (m, ..., d/m): sub-vector j of every point at ``[j]``."""
    return jnp.moveaxis(x.reshape(x.shape[:-1] + (m, -1)), -2, 0)


@functools.partial(jax.jit,
                   static_argnames=("tau", "eps0", "decay", "dtype", "fault"))
def run_chunk(w: jax.Array, t0: jax.Array, chunk: jax.Array, *, tau: int,
              eps0: float, decay: float, dtype=jnp.float32,
              fault: str | None = None) -> jax.Array:
    """Sub-codebooks (m, k, d/m) after the windows of ``chunk`` (M, n, d),
    starting at local step ``t0``."""
    return jax.vmap(lambda wj, cj: vq.run_chunk(
        wj, t0, cj, tau=tau, eps0=eps0, decay=decay, dtype=dtype,
        fault=fault))(w, split(chunk, w.shape[0]))


@functools.partial(jax.jit, static_argnames=("dtype",))
def distortion(w: jax.Array, ev: jax.Array, dtype=jnp.float32) -> jax.Array:
    """Mean over the held-out points ``ev`` (M, n_eval, d) of the sum over
    sub-spaces of the squared distance to the nearest sub-code."""
    evs = split(ev, w.shape[0])
    return sum(vq.distortion(w[j], evs[j], dtype=dtype)
               for j in range(w.shape[0]))


def follow(w0: jax.Array, chunks: list, ev: jax.Array, *, tau: int,
           eps0: float, decay: float, dtype=jnp.float32,
           fault: str | None = None) -> tuple[list, list[float]]:
    """Follow the program through ``chunks``: the sub-codebooks after each,
    and the held-out distortion there."""
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
