"""Fused Pallas kernels for the VQ hot path — one dispatch, no round trips.

Three kernels on top of the ``vq_assign.py`` pair:

  * ``vq_delta_blocked_pallas`` — assignment + delta accumulation (counts,
    zsum, min-dist) in ONE Pallas dispatch for the blocked (``kappa*d`` >
    VMEM) regime: the grid is ``(2*kappa_blocks, batch_blocks)`` with the
    batch axis minor — an outer *distance* sweep (j < K) streams codebook
    blocks and keeps the running (min, argmin) for the WHOLE batch in two
    VMEM-resident ``(batch, 1)`` outputs, then an outer *accumulate* sweep
    (j >= K) re-streams each codebook block and folds every batch block's
    one-hot contribution into that block's (counts, zsum) — output
    revisits stay consecutive, so the accumulators live in VMEM until
    their single flush.

  * ``vq_window_pallas`` — the engine's inner loop: ``tau`` SEQUENTIAL
    eq.-1 steps (batch of one point each) fused into one dispatch with the
    codebook resident in VMEM for the whole window.  Each step runs the
    same float ops as the engine's per-step route (d2 via MXU contraction
    against carried row norms, strict argmin, ``row - eps*(row - z)`` on
    the winning row alone), so the fused window is bit-identical to the
    per-step scan it replaces — every per-row reduction and product is
    independent of the seven padding rows the per-step kernel carries.
    ``tests/test_step_route.py`` and ``tests/test_autotune.py`` pin that
    on XLA:CPU.

  * ``pq_window_pallas`` — the same window for a product quantizer: m
    sub-codebooks of k codes over d/m-dimensional sub-vectors, all
    resident, each step one nearest-code search and one one-row update in
    every sub-space.

Block sizes come from ``kernels.autotune``; shapes are padded by ``ops.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.vq_assign import BIG


def _fused_delta_kernel(z_ref, w_ref, assign_ref, mind_ref, counts_ref,
                        zsum_ref, *, bm: int, bk: int, kb: int, n_valid: int,
                        kappa_valid: int):
    """Grid = (2*kb, batch_blocks); batch is the minor axis.

    Outer steps j < kb:   distance sweep — codebook block j vs batch block
                          i, running (min, argmin) updated in the resident
                          (batch, 1) outputs.
    Outer steps j >= kb:  accumulate sweep — codebook block j-kb gathers
                          counts/zsum from every batch block i (consecutive
                          revisits of one (bk, ·) output block).
    """
    j = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(jnp.logical_and(j == 0, i == 0))
    def _init_running():
        # the (batch, 1) min/arg outputs have constant index maps: one
        # block covering the whole array, resident for the entire grid
        mind_ref[...] = jnp.full_like(mind_ref, BIG)
        assign_ref[...] = jnp.zeros_like(assign_ref)

    rows = pl.ds(i * bm, bm)

    @pl.when(j < kb)
    def _distance_sweep():
        z = z_ref[...].astype(jnp.float32)           # (bm, d)
        w = w_ref[...].astype(jnp.float32)           # (bk, d)
        z2 = jnp.sum(z * z, axis=1, keepdims=True)
        w2 = jnp.sum(w * w, axis=1)[None, :]
        # ``z @ w.T`` rounds like the ``squared_distances`` oracle (see
        # the note in ``vq_assign._assign_kernel``)
        d2 = z2 - 2.0 * (z @ w.T) + w2                # (bm, bk)
        col = j * bk + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
        d2 = jnp.where(col < kappa_valid, d2, BIG)
        blk_arg = jnp.argmin(d2, axis=1).astype(jnp.int32)[:, None]
        blk_min = jnp.min(d2, axis=1)[:, None]
        cur_min = mind_ref[rows, :]
        cur_arg = assign_ref[rows, :]
        better = blk_min < cur_min
        mind_ref[rows, :] = jnp.where(better, blk_min, cur_min)
        assign_ref[rows, :] = jnp.where(better, j * bk + blk_arg, cur_arg)

    @pl.when(j >= kb)
    def _accumulate_sweep():
        @pl.when(i == 0)
        def _zero_block():
            counts_ref[...] = jnp.zeros_like(counts_ref)
            zsum_ref[...] = jnp.zeros_like(zsum_ref)

        z = z_ref[...].astype(jnp.float32)           # (bm, d)
        arg = assign_ref[rows, :]                     # (bm, 1) final argmin
        row = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
        valid = row < n_valid
        local = arg - (j - kb) * bk                   # block-local code id
        onehot = (local == jax.lax.broadcasted_iota(
            jnp.int32, (bm, bk), 1)).astype(jnp.float32)
        onehot = jnp.where(valid, onehot, 0.0)
        counts_ref[...] += jnp.sum(onehot, axis=0)[:, None]
        # (bk, bm) x (bm, d) scatter-add as an MXU matmul
        zsum_ref[...] += jax.lax.dot_general(
            onehot, z, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def vq_delta_blocked_pallas(z: jax.Array, w: jax.Array, *, bm: int, bk: int,
                            n_valid: int | None = None,
                            kappa_valid: int | None = None,
                            interpret: bool = False):
    """Fused blocked assign+delta: one dispatch for any ``kappa * d``.

    (batch, d), (kappa, d) -> (assign (batch,) i32, mind (batch,) f32,
    counts (kappa,) f32, zsum (kappa, d) f32).
    ``batch % bm == 0`` and ``kappa % bk == 0`` required (``ops.py`` pads).
    The residency plan holds only ``O(bm*d + bk*d + bm*bk + batch)`` bytes
    — never the full codebook — which is what ``ops.delta_vmem_bytes(...,
    bk=...)`` budgets.
    """
    batch, d = z.shape
    kappa, _ = w.shape
    n_valid = batch if n_valid is None else n_valid
    kappa_valid = kappa if kappa_valid is None else kappa_valid
    kb = kappa // bk

    assign, mind, counts, zsum = pl.pallas_call(
        functools.partial(_fused_delta_kernel, bm=bm, bk=bk, kb=kb,
                          n_valid=n_valid, kappa_valid=kappa_valid),
        name="vq_delta_blocked",
        grid=(2 * kb, batch // bm),
        in_specs=[
            pl.BlockSpec((bm, d), lambda j, i: (i, 0)),
            pl.BlockSpec((bk, d), lambda j, i: (j % kb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((batch, 1), lambda j, i: (0, 0)),
            pl.BlockSpec((batch, 1), lambda j, i: (0, 0)),
            pl.BlockSpec((bk, 1), lambda j, i: (jnp.maximum(j - kb, 0), 0)),
            pl.BlockSpec((bk, d), lambda j, i: (jnp.maximum(j - kb, 0), 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, 1), jnp.int32),
            jax.ShapeDtypeStruct((batch, 1), jnp.float32),
            jax.ShapeDtypeStruct((kappa, 1), jnp.float32),
            jax.ShapeDtypeStruct((kappa, d), jnp.float32),
        ],
        interpret=interpret,
    )(z, w)
    return assign[:, 0], mind[:, 0], counts[:, 0], zsum


def _window_kernel(z_ref, w0_ref, eps_ref, wout_ref, w2_ref, *, tau: int):
    """One fused window: tau sequential eq.-1 steps, codebook VMEM-resident.

    z_ref:   (tau, d)    the window's point stream
    w0_ref:  (kappa, d)  prototypes entering the window
    eps_ref: (tau,)      precomputed Robbins-Monro steps (f32, in SMEM)
    wout_ref:(kappa, d)  prototypes after the window; the resident codebook
    w2_ref:  (1, kappa)  VMEM scratch: the resident rows' squared norms

    The codebook is copied into ``wout_ref`` and its row norms into
    ``w2_ref`` once, at window entry.  A step then touches one row: it
    scores its point against the resident codebook with the kept norms,
    takes the winning code as a scalar, loads that row, updates it, stores
    it back, and refreshes that row's norm with a lane select.  Nothing of
    (kappa, d) size is formed per step beyond the distance product.  The
    step reads its point and step size from the refs at ``t``: Mosaic
    lowers a dynamic ref index, not a ``dynamic_slice`` of a loaded value.

    Bitwise equality with the per-step scan is load-bearing on XLA:CPU (the
    mesh-vs-oracle tier-1 pins ride on it), and two compilation artifacts
    can silently break it:

      * SHAPES: XLA's reduction/matmul emission is shape-dependent, so the
        distance ops here must see the SAME shapes as ``_assign_kernel``
        does on the per-step route.  On the interpret backend ``ops.py``
        clamps the batch-of-one block to one row (no MXU to align for), so
        each step here computes z2/dot/argmin on the matching (1, d)
        row, and the cross term is spelled ``z @ w.T`` exactly as
        ``core.vq.squared_distances`` writes it — a dim-1/dim-1
        ``dot_general`` accumulates in a different order on XLA:CPU and
        flips near-tie argmins (observed gap: ~2e-7 on unit-scale data).
      * FMA CONTRACTION: the update is left as the plain ``w - eps*h``
        the scan body writes — LLVM contracts BOTH loop contexts into the
        same fma.  Do not "improve" the rounding here (e.g. forcing the
        product to round first): eagerly-executed one-step programs round
        differently from either loop, and matching those breaks the
        jitted-scan equality that actually matters.

    Updating only the winner is the same arithmetic as the minibatch
    form ``w - eps*(counts*w - zsum)`` at a batch of one: on a losing row
    that returns ``w`` unchanged, on the winning row it is
    ``w - eps*(w - z)``.
    """
    kappa = w0_ref.shape[0]
    w0 = w0_ref[...].astype(jnp.float32)
    wout_ref[...] = w0
    w2_ref[...] = jnp.sum(w0 * w0, axis=1)[None, :]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, kappa), 1)

    def step(t, carry):
        z = z_ref[pl.ds(t, 1), :].astype(jnp.float32)            # (1, d)
        z2 = jnp.sum(z * z, axis=1, keepdims=True)               # (1, 1)
        w = wout_ref[...]
        d2 = z2 - 2.0 * (z @ w.T) + w2_ref[...]                  # (1, kappa)
        arg = jnp.argmin(d2, axis=1, keepdims=True)              # (1, 1)
        k = arg[0, 0]
        row = wout_ref[pl.ds(k, 1), :]                           # (1, d)
        h = row - z
        row = row - eps_ref[t] * h
        wout_ref[pl.ds(k, 1), :] = row
        w2_ref[...] = jnp.where(lane == arg,
                                jnp.sum(row * row, axis=1, keepdims=True),
                                w2_ref[...])
        return carry

    jax.lax.fori_loop(0, tau, step, 0)


def vq_window_pallas(zwin: jax.Array, w0: jax.Array, eps: jax.Array, *,
                     interpret: bool = False) -> jax.Array:
    """(tau, d), (kappa, d), (tau,) -> w after tau fused sequential steps."""
    tau, d = zwin.shape
    kappa, _ = w0.shape
    return pl.pallas_call(
        functools.partial(_window_kernel, tau=tau),
        name="vq_window",
        grid=(1,),
        in_specs=[
            pl.BlockSpec((tau, d), lambda i: (0, 0)),
            pl.BlockSpec((kappa, d), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((kappa, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((kappa, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, kappa), jnp.float32)],
        interpret=interpret,
    )(zwin, w0.astype(jnp.float32), eps.reshape(tau).astype(jnp.float32))


def _pq_window_kernel(z_ref, w0_ref, eps_ref, wout_ref, *, tau: int):
    """One fused product-quantizer window: tau sequential eq.-1 steps in
    each of the m sub-spaces at once, with the same step size in all.

    z_ref:   (m, d/m, tau)  the window's points, one per lane column
    w0_ref:  (m, d/m, k)    sub-codebooks entering the window
    eps_ref: (tau,)         precomputed Robbins-Monro steps (f32, in SMEM)
    wout_ref:(m, d/m, k)    sub-codebooks after the window; resident

    Sub-codebook j is the (d/m, k) slab ``[j]``: its coordinates on
    sublanes and its codes on lanes, so at d/m = 8 a sub-space is one
    sublane tile and the search sums a tile's sublanes.  The sub-distances
    are float32 differences, never a product.  A step broadcasts its
    point's column along the lanes, takes the lowest-index nearest code of
    every slab, and moves that one column of each slab by ``eps * (w -
    z)``; every other column is left as it is.  The steps are unrolled, so
    a point's column is a static lane slice.
    """
    m, _, k = w0_ref.shape
    wout_ref[...] = w0_ref[...]
    code = jax.lax.broadcasted_iota(jnp.int32, (m, 1, k), 2)
    for t in range(tau):
        z = z_ref[:, :, t:t + 1]                               # (m, ds, 1)
        w = wout_ref[...]
        h = w - z                                              # (m, ds, k)
        d2 = jnp.sum(h * h, axis=1, keepdims=True)             # (m, 1, k)
        best = jnp.min(d2, axis=2, keepdims=True)              # (m, 1, 1)
        arg = jnp.min(jnp.where(d2 == best, code, k), axis=2, keepdims=True)
        wout_ref[...] = jnp.where(code == arg, w - eps_ref[t] * h, w)


def pq_window_pallas(zwin: jax.Array, w0: jax.Array, eps: jax.Array, *,
                     interpret: bool = False) -> jax.Array:
    """(tau, d), (m, k, d/m), (tau,) -> the sub-codebooks (m, k, d/m) after
    tau fused sequential steps."""
    tau, _ = zwin.shape
    m, k, ds = w0.shape
    zt = zwin.astype(jnp.float32).reshape(tau, m, ds).transpose(1, 2, 0)
    wt = jnp.swapaxes(w0.astype(jnp.float32), 1, 2)
    out = pl.pallas_call(
        functools.partial(_pq_window_kernel, tau=tau),
        name="pq_window",
        grid=(1,),
        in_specs=[
            pl.BlockSpec((m, ds, tau), lambda i: (0, 0, 0)),
            pl.BlockSpec((m, ds, k), lambda i: (0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((m, ds, k), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, ds, k), jnp.float32),
        interpret=interpret,
    )(zt, wt, eps.reshape(tau).astype(jnp.float32))
    return jnp.swapaxes(out, 1, 2)
