"""Pallas TPU kernels for the VQ hot spot: fused distance + argmin (+ delta).

The paper's compute bottleneck is the nearest-prototype search over the data
stream.  On TPU we express ``||z - w||^2 = ||z||^2 - 2 z.w^T + ||w||^2`` so
the dominant cost is a (batch, d) x (d, kappa) matmul on the MXU, and fuse
the argmin reduction (and, in the delta kernel, the one-hot scatter-add) into
the same VMEM-resident pass so distances are never materialized in HBM.

Two kernels:

  * ``vq_assign_kernel`` — blocked over (batch, kappa): supports arbitrarily
    large codebooks.  Grid is (batch_blocks, kappa_blocks) with kappa minor,
    keeping a running (min, argmin) in the revisited output block.
  * ``vq_delta_kernel``  — grid over batch blocks with the full codebook
    resident in VMEM: computes assignments AND accumulates per-prototype
    (counts, zsum) in one pass — the whole minibatch VQ update's memory
    traffic is ``batch*d + kappa*d`` instead of ``batch*kappa``.

Block sizes default to MXU-aligned 128s; all shapes are padded by ``ops.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BIG = 3.0e38  # python float: safe to close over in kernel bodies


def _assign_kernel(z_ref, w_ref, z2_ref, w2_ref, assign_ref, mind_ref,
                   *, bk: int, kappa_valid: int):
    """Grid = (batch_blocks, kappa_blocks); kappa is the minor axis.

    z_ref:  (bm, d)   batch block (revisited across kappa blocks)
    w_ref:  (bk, d)   codebook block
    z2_ref: (bm, 1)   precomputed ||z||^2
    w2_ref: (1, bk)   precomputed ||w||^2 (BIG on padded rows)
    assign_ref/mind_ref: (bm, 1) running argmin / min, revisited.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        mind_ref[...] = jnp.full_like(mind_ref, BIG)
        assign_ref[...] = jnp.zeros_like(assign_ref)

    z = z_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    # (bm, bk) distances for this codebook block — MXU matmul + rank-1 terms.
    # The cross term is spelled ``z @ w.T`` (not a dim-1/dim-1 dot_general):
    # XLA:CPU accumulates the two contractions in different orders, and the
    # bitwise window-kernel-vs-scan pins need the SAME rounding as the
    # ``core.vq.squared_distances`` oracle, which writes ``z @ w.T``.
    d2 = z2_ref[...] - 2.0 * (z @ w.T) + w2_ref[...]

    # mask out padded codebook rows (global kappa index >= kappa_valid)
    col = j * bk + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    d2 = jnp.where(col < kappa_valid, d2, BIG)

    blk_arg = jnp.argmin(d2, axis=1).astype(jnp.int32)  # (bm,)
    blk_min = jnp.min(d2, axis=1)                       # (bm,)
    better = blk_min < mind_ref[..., 0]
    mind_ref[..., 0] = jnp.where(better, blk_min, mind_ref[..., 0])
    assign_ref[..., 0] = jnp.where(better, j * bk + blk_arg, assign_ref[..., 0])


def vq_assign_pallas(z: jax.Array, w: jax.Array, *, bm: int = 128,
                     bk: int = 128, kappa_valid: int | None = None,
                     w2: jax.Array | None = None,
                     interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """(batch, d), (kappa, d) -> assign (batch,) int32, mindist (batch,) f32.

    batch % bm == 0 and kappa % bk == 0 are required (ops.py pads).
    ``w2`` is the rows' squared norms, (1, kappa) f32, when the caller
    keeps them; without it they are computed here.
    """
    batch, d = z.shape
    kappa, _ = w.shape
    kappa_valid = kappa if kappa_valid is None else kappa_valid
    z32 = z.astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    z2 = jnp.sum(z32 * z32, axis=1, keepdims=True)          # (batch, 1)
    if w2 is None:
        w2 = jnp.sum(w32 * w32, axis=1)[None, :]            # (1, kappa)

    grid = (batch // bm, kappa // bk)
    assign, mind = pl.pallas_call(
        functools.partial(_assign_kernel, bk=bk, kappa_valid=kappa_valid),
        name="vq_assign",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bk), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, 1), jnp.int32),
            jax.ShapeDtypeStruct((batch, 1), jnp.float32),
        ],
        interpret=interpret,
    )(z, w, z2, w2)
    return assign[:, 0], mind[:, 0]


def _delta_kernel(z_ref, w_ref, counts_ref, zsum_ref, mind_ref,
                  *, bm: int, n_valid: int):
    """Grid = (batch_blocks,); full codebook resident in VMEM.

    Accumulates counts (kappa, 1) and zsum (kappa, d) across batch blocks via
    revisited output blocks; also writes per-row min distance (for eq. 2).
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)
        zsum_ref[...] = jnp.zeros_like(zsum_ref)

    z = z_ref[...].astype(jnp.float32)           # (bm, d)
    w = w_ref[...].astype(jnp.float32)           # (kappa, d)
    z2 = jnp.sum(z * z, axis=1, keepdims=True)
    w2 = jnp.sum(w * w, axis=1)[None, :]
    # ``z @ w.T`` (not a dim-1/dim-1 dot_general) — rounds exactly like the
    # ``core.vq.squared_distances`` oracle; see the note in ``_assign_kernel``
    d2 = z2 - 2.0 * (z @ w.T) + w2               # (bm, kappa)

    row = i * bm + jax.lax.broadcasted_iota(jnp.int32, (z.shape[0], 1), 0)
    valid = row < n_valid                         # (bm, 1)

    mind_ref[...] = jnp.where(valid, jnp.min(d2, axis=1, keepdims=True), 0.0)
    arg = jnp.argmin(d2, axis=1)                  # (bm,)
    onehot = (arg[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (z.shape[0], w.shape[0]), 1)).astype(jnp.float32)
    onehot = jnp.where(valid, onehot, 0.0)        # mask padded rows

    counts_ref[...] += jnp.sum(onehot, axis=0)[:, None]
    # (kappa, bm) x (bm, d) scatter-add as an MXU matmul
    zsum_ref[...] += jax.lax.dot_general(
        onehot, z, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def vq_delta_pallas(z: jax.Array, w: jax.Array, *, bm: int = 128,
                    n_valid: int | None = None, interpret: bool = False
                    ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(batch, d), (kappa, d) -> counts (kappa,), zsum (kappa, d), mind (batch,).

    Requires batch % bm == 0 (ops.py pads) and kappa*d to fit in VMEM.
    """
    batch, d = z.shape
    kappa, _ = w.shape
    n_valid = batch if n_valid is None else n_valid

    counts, zsum, mind = pl.pallas_call(
        functools.partial(_delta_kernel, bm=bm, n_valid=n_valid),
        name="vq_delta",
        grid=(batch // bm,),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i: (i, 0)),
            pl.BlockSpec((kappa, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((kappa, 1), lambda i: (0, 0)),
            pl.BlockSpec((kappa, d), lambda i: (0, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kappa, 1), jnp.float32),
            jax.ShapeDtypeStruct((kappa, d), jnp.float32),
            jax.ShapeDtypeStruct((batch, 1), jnp.float32),
        ],
        interpret=interpret,
    )(z, w)
    return counts[:, 0], zsum, mind[:, 0]
