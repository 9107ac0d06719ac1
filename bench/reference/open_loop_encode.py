"""Plain reference of nearest-code encoding, independent of the program.

``sqdist`` gives float32 squared distances with the cross term at
``Precision.HIGHEST`` (exact to float32 rounding).  ``assign_bf16`` is the
control: the same lookup with operands, products and distances in
bfloat16, the precision below the configuration's.  It has the lookup's
call shape, so it can stand in the program's place.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 1024


@jax.jit
def sqdist(z: jax.Array, w: jax.Array) -> jax.Array:
    z = z.astype(jnp.float32)
    w = w.astype(jnp.float32)
    return (jnp.sum(z * z, axis=1, keepdims=True)
            - 2 * jnp.dot(z, w.T, precision=jax.lax.Precision.HIGHEST)
            + jnp.sum(w * w, axis=1)[None])


@jax.jit
def _assign_bf16(z, w):
    z = z.astype(jnp.bfloat16)
    w = w.astype(jnp.bfloat16)
    d2 = (jnp.sum(z * z, axis=1, keepdims=True) - 2 * (z @ w.T)
          + jnp.sum(w * w, axis=1)[None])
    return (jnp.argmin(d2, axis=1).astype(jnp.int32),
            jnp.min(d2, axis=1).astype(jnp.float32))


def assign_bf16(z, w):
    """(codes, distances) computed in bfloat16: the control lookup."""
    return _assign_bf16(jnp.asarray(z), jnp.asarray(w))


@jax.jit
def _gaps(z, w, codes, mind):
    d2 = sqdist(z, w)
    got = jnp.take_along_axis(d2, codes[:, None], axis=1)[:, 0]
    best = jnp.argmin(d2, axis=1)
    zz, ww = jnp.sum(z * z, axis=1), jnp.sum(w * w, axis=1)
    over = got - jnp.min(d2, axis=1)
    scale = zz + ww[codes]
    return (over / scale, jnp.abs(mind - got) / scale,
            over / (scale + zz + ww[best]))


def gaps(z: np.ndarray, w: jax.Array, codes: np.ndarray, mind: np.ndarray
         ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per row: how far the served code's distance lies above the nearest
    one, and how far the served distance is off the served code's true
    distance, both over the row's scale ``|z|^2 + |w_code|^2``; and the
    first over the sum of the served and the nearest code's scales, which
    products at one bfloat16 pass keep under 2^-8."""
    n = len(z)
    pad = (-n) % BLOCK
    z = np.concatenate([z, np.zeros((pad, z.shape[1]), np.float32)])
    codes = np.concatenate([codes, np.zeros(pad, np.int32)])
    mind = np.concatenate([mind, np.zeros(pad, np.float32)])
    out = [_gaps(z[i:i + BLOCK], w, codes[i:i + BLOCK], mind[i:i + BLOCK])
           for i in range(0, len(z), BLOCK)]
    return tuple(np.concatenate([np.asarray(o[j]) for o in out])[:n]
                 for j in range(3))
