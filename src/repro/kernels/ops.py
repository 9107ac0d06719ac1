"""Jit'd public wrappers around the Pallas VQ kernels.

Handles padding to MXU-aligned block multiples, picks interpret mode
automatically off-TPU (the kernel body then runs as pure-python/jnp on CPU —
bit-identical semantics, which is what the allclose tests exercise), and
exposes the same signatures as the ``ref.py`` oracles.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels import vq_assign as _k
from repro.kernels import vq_fused as _f

# Conservative per-core VMEM budget for kernel residency planning.  TPU cores
# have ~16 MiB of VMEM (pallas guide §Memory Spaces); half of it is left for
# double-buffered input blocks, scratch, and the compiler's own staging.
DEFAULT_VMEM_BUDGET_BYTES = 8 * 1024 * 1024


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def vmem_budget_bytes(budget_bytes: int | None = None) -> int:
    """The VMEM budget used to route between kernels.

    Explicit argument > ``REPRO_VMEM_BUDGET_BYTES`` env var > the default.
    """
    if budget_bytes is not None:
        if budget_bytes <= 0:
            raise ValueError(f"vmem budget must be > 0, got {budget_bytes}")
        return budget_bytes
    env = os.environ.get("REPRO_VMEM_BUDGET_BYTES", "")
    return int(env) if env else DEFAULT_VMEM_BUDGET_BYTES


def delta_vmem_bytes(kappa: int, d: int, *, bm: int = 128,
                     bk: int | None = None, batch: int | None = None,
                     dtype_bytes: int = 4) -> int:
    """VMEM residency of one delta-kernel grid step — the ONE cost model the
    runtime router and the autotuner share.

    ``bk=None`` (or ``bk >= kappa``): the full-codebook ``vq_delta`` kernel —
    codebook + zsum accumulator (both (kappa, d)), the counts column, one
    (bm, d) batch block, and the (bm, kappa) distance/one-hot tiles.

    ``bk < kappa``: the fused blocked assign+delta kernel — one (bm, d)
    point block, the (bk, d) codebook block and its (bk, d)+(bk, 1)
    accumulators, the (bm, bk) distance/one-hot tiles, and the running
    (batch, 1) argmin/min outputs that stay resident for the whole grid
    (``batch`` defaults to ``bm`` when the caller has not fixed it).
    """
    if bk is None or bk >= kappa:
        return dtype_bytes * (2 * kappa * d + kappa + bm * d + 2 * bm * kappa)
    rows = bm if batch is None else max(batch, bm)
    return dtype_bytes * (bm * d + 2 * bk * d + bk + 2 * bm * bk + 2 * rows)


def delta_fits_vmem(kappa: int, d: int, *, bm: int = 128,
                    budget_bytes: int | None = None) -> bool:
    """Can the full-codebook ``vq_delta`` kernel hold ``kappa*d`` in VMEM?"""
    return delta_vmem_bytes(kappa, d, bm=bm) <= vmem_budget_bytes(budget_bytes)


def window_vmem_bytes(kappa: int, d: int, tau: int, *,
                      dtype_bytes: int = 4) -> int:
    """The router's conservative bound on the fused window kernel's
    residency: the (tau, d) point stream plus its norms/steps, 4 (kappa,
    d)-sized terms and 2 kappa-sized columns.  The kernel holds less: it
    updates one row a step and forms no (kappa, d) one-hot, ``zsum`` or
    ``h``.  A tighter count would move shapes (kappa=4,096 at d=128) from
    the per-step route to the window route."""
    return dtype_bytes * (tau * (d + 2) + 4 * kappa * d + 2 * kappa)


def window_fits_vmem(kappa: int, d: int, tau: int, *,
                     budget_bytes: int | None = None) -> bool:
    """Can a whole tau-step window run codebook-resident in one dispatch?"""
    return (window_vmem_bytes(kappa, d, tau)
            <= vmem_budget_bytes(budget_bytes))


def codebook_fits_vmem(kappa: int, d: int, *,
                       budget_bytes: int | None = None) -> bool:
    """Does a replicated (kappa, d) f32 codebook fit one device's budget?
    (The serving lookup shards kappa across devices when it does not.)"""
    return 4 * kappa * d <= vmem_budget_bytes(budget_bytes)


def _bm_floor(interpret: bool) -> int:
    """Minimum batch-block rows.  Real TPUs want >= 8 rows for sublane
    alignment; the interpret backend has no such constraint — and the fused
    window kernel's bitwise contract needs the batch-of-one per-step block
    to keep its true single-row shape there, because XLA:CPU's
    reduction/matmul emission is shape-dependent (see
    ``vq_fused._window_kernel``)."""
    return 1 if interpret else 8


def _pad_rows(x: jax.Array, mult: int) -> jax.Array:
    pad = (-x.shape[0]) % mult
    if pad == 0:
        return x
    return jnp.pad(x, ((0, pad), (0, 0)))


def _tiles(z: jax.Array, w: jax.Array, bm: int | None, bk: int | None,
           kind: str, budget_bytes: int | None = None) -> tuple[int, int]:
    """Resolve (bm, bk): explicit values win, ``None`` comes from the
    autotuner (legacy 128s when the tuner is off).  Runs at trace time —
    shapes are static — so jitted callers pay nothing per step."""
    if bm is None or bk is None:
        cfg = autotune.pick_tiles(z.shape[0], w.shape[0], w.shape[1],
                                  kind=kind, budget_bytes=budget_bytes)
        bm = cfg.bm if bm is None else bm
        bk = cfg.bk if bk is None else bk
    return bm, bk


@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def _vq_assign(z, w, w2=None, *, bm: int, bk: int, interpret: bool):
    batch, kappa = z.shape[0], w.shape[0]
    bm_ = min(bm, max(_bm_floor(interpret), batch))
    zp = _pad_rows(z, bm_)
    wp = _pad_rows(w, bk)
    if w2 is not None and wp.shape[0] > kappa:
        w2 = jnp.pad(w2, ((0, 0), (0, wp.shape[0] - kappa)))
    assign, mind = _k.vq_assign_pallas(zp, wp, bm=bm_, bk=min(bk, wp.shape[0]),
                                       kappa_valid=kappa, w2=w2,
                                       interpret=interpret)
    return assign[:batch], mind[:batch]


def vq_assign(z: jax.Array, w: jax.Array, *, w2: jax.Array | None = None,
              bm: int | None = None, bk: int | None = None,
              interpret: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """Nearest-prototype assignment; same contract as ``ref.vq_assign_ref``.

    ``w2``: the rows' squared norms, (1, kappa) f32, for a caller that
    keeps them up to date (the engine's per-step route); omitted, the
    kernel's wrapper computes them."""
    interpret = _interpret_default() if interpret is None else interpret
    bm, bk = _tiles(z, w, bm, bk, "assign")
    return _vq_assign(z, w, w2, bm=bm, bk=bk, interpret=interpret)


def assign_vmem_bytes(kappa: int, d: int, *, bm: int, bk: int,
                      dtype_bytes: int = 4) -> int:
    """VMEM residency of one ``vq_assign`` grid step: the (bm, d) point
    block and its (bm, 1) norms, the (bk, d) codebook block and its (1,
    bk) norms, the (bm, bk) distance and index tiles, and the two (bm, 1)
    running outputs."""
    return dtype_bytes * (bm * d + bm + bk * d + bk + 2 * bm * bk + 2 * bm)


def vq_assign_routed(z: jax.Array, w: jax.Array, *,
                     w2: jax.Array | None = None,
                     budget_bytes: int | None = None,
                     interpret: bool | None = None
                     ) -> tuple[jax.Array, jax.Array]:
    """``vq_assign`` with VMEM-aware tiles (same contract).

    When the whole (kappa, d) codebook fits the VMEM budget beside the
    small tiles, it is one block: a grid of one along kappa, one pass over
    the codebook.  Otherwise the tuner's blocked grid runs.  The tuner's
    candidates stop at ``bk`` = 512, so leaving a resident codebook to it
    would split the search into kappa / 512 grid steps."""
    kappa, d = w.shape
    bm, bk = _tiles(z, w, None, None, "assign", budget_bytes=budget_bytes)
    if (assign_vmem_bytes(kappa, d, bm=min(bm, max(8, z.shape[0])), bk=kappa)
            <= vmem_budget_bytes(budget_bytes)):
        bk = kappa
    return vq_assign(z, w, w2=w2, bm=bm, bk=bk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def _vq_delta(z, w, *, bm: int, interpret: bool):
    batch = z.shape[0]
    bm_ = min(bm, max(_bm_floor(interpret), batch))
    zp = _pad_rows(z, bm_)
    counts, zsum, _ = _k.vq_delta_pallas(zp, w, bm=bm_, n_valid=batch,
                                         interpret=interpret)
    return counts, zsum


def vq_delta(z: jax.Array, w: jax.Array, *, bm: int | None = None,
             interpret: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """Fused minibatch displacement stats; contract of ``ref.vq_delta_ref``."""
    interpret = _interpret_default() if interpret is None else interpret
    if bm is None:      # explicit bm skips the tuner entirely (bk is unused
        bm, _ = _tiles(z, w, None, None, "delta")  # here, so no resolution)
    return _vq_delta(z, w, bm=bm, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def _distortion(z, w, *, bm: int, interpret: bool):
    batch = z.shape[0]
    bm_ = min(bm, max(_bm_floor(interpret), batch))
    zp = _pad_rows(z, bm_)
    _, _, mind = _k.vq_delta_pallas(zp, w, bm=bm_, n_valid=batch,
                                    interpret=interpret)
    return jnp.sum(mind) / batch


def distortion(z: jax.Array, w: jax.Array, *, bm: int | None = None,
               interpret: bool | None = None) -> jax.Array:
    """Mean min-distance (paper eq. 2 per worker) via the fused kernel."""
    interpret = _interpret_default() if interpret is None else interpret
    if bm is None:
        bm, _ = _tiles(z, w, None, None, "delta")
    return _distortion(z, w, bm=bm, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def _vq_delta_blocked(z, w, *, bm: int, bk: int, interpret: bool):
    batch, kappa = z.shape[0], w.shape[0]
    bm_ = min(bm, max(_bm_floor(interpret), batch))
    zp = _pad_rows(z, bm_)
    wp = _pad_rows(w, bk)
    _, _, counts, zsum = _f.vq_delta_blocked_pallas(
        zp, wp, bm=bm_, bk=min(bk, wp.shape[0]), n_valid=batch,
        kappa_valid=kappa, interpret=interpret)
    return counts[:kappa], zsum[:kappa]


def vq_delta_blocked(z: jax.Array, w: jax.Array, *, bm: int | None = None,
                     bk: int | None = None, interpret: bool | None = None
                     ) -> tuple[jax.Array, jax.Array]:
    """Fused blocked assign+delta (one dispatch, any ``kappa*d``);
    returns ``(counts, zsum)``."""
    interpret = _interpret_default() if interpret is None else interpret
    bm, bk = _tiles(z, w, bm, bk, "delta_blocked")
    return _vq_delta_blocked(z, w, bm=bm, bk=bk, interpret=interpret)


def vq_delta_routed(z: jax.Array, w: jax.Array, *, bm: int | None = None,
                    bk: int | None = None, budget_bytes: int | None = None,
                    interpret: bool | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """``vq_delta`` with VMEM-aware routing (same contract as ``vq_delta``).

    When the codebook fits the VMEM budget, the full-codebook kernel runs;
    when ``kappa*d`` is too large, the fused blocked assign+delta kernel
    keeps everything in one dispatch.
    """
    kappa, d = w.shape
    bm, bk = _tiles(z, w, bm, bk, "delta", budget_bytes=budget_bytes)
    if delta_fits_vmem(kappa, d, bm=min(bm, max(8, z.shape[0])),
                       budget_bytes=budget_bytes):
        return vq_delta(z, w, bm=bm, interpret=interpret)
    return vq_delta_blocked(z, w, bm=bm, bk=bk, interpret=interpret)


def vq_window(zwin: jax.Array, w0: jax.Array, eps: jax.Array, *,
              interpret: bool | None = None) -> jax.Array:
    """One fused window: tau sequential eq.-1 steps in a single dispatch.

    Bit-identical to scanning ``vq_assign`` and the one-row eq.-1 update
    over the rows of ``zwin``, the engine's per-step route
    (``tests/test_step_route.py`` pins this).  Callers check
    ``window_fits_vmem`` first — the codebook stays resident throughout.
    """
    interpret = _interpret_default() if interpret is None else interpret
    return _f.vq_window_pallas(zwin, w0, eps, interpret=interpret)


def pq_window(zwin: jax.Array, w0: jax.Array, eps: jax.Array, *,
              interpret: bool | None = None) -> jax.Array:
    """One fused product-quantizer window: tau sequential eq.-1 steps in
    each of the m sub-spaces of ``w0`` (m, k, d/m), in a single dispatch.

    The same steps as scanning ``core.vq.pq_H`` over the rows of ``zwin``;
    ties go to the lowest code.  The sub-codebooks stay resident
    throughout: 4 * d * k bytes, 128 KiB at PQ16x256 on SIFT."""
    interpret = _interpret_default() if interpret is None else interpret
    return _f.pq_window_pallas(zwin, w0, eps, interpret=interpret)


def vq_minibatch_step(z: jax.Array, w: jax.Array, eps: jax.Array,
                      *, budget_bytes: int | None = None,
                      interpret: bool | None = None) -> jax.Array:
    """One fused minibatch VQ update: w <- w - (eps/|B|) * (counts*w - zsum).

    Routed through ``vq_delta_routed`` so large-kappa codebooks take the
    blocked kernel instead of blowing the full-codebook VMEM plan.
    """
    counts, zsum = vq_delta_routed(z, w, budget_bytes=budget_bytes,
                                   interpret=interpret)
    delta = counts[:, None] * w.astype(jnp.float32) - zsum
    return (w.astype(jnp.float32) - (eps / z.shape[0]) * delta).astype(w.dtype)
