"""Sharded batched codebook lookup behind the mesh machinery.

Three execution plans for ``argmin_l ||z - w_l||^2`` over a query batch,
picked per codebook by the VMEM routing helper in ``kernels.ops``:

  * ``direct``      — one device: the blocked ``vq_assign`` Pallas kernel.
  * ``shard_batch`` — the codebook fits one device's VMEM budget: replicate
    w, shard the query batch over the mesh, no collectives (the serving
    analogue of the paper's data-parallel split).
  * ``shard_kappa`` — ``kappa*d`` exceeds the budget: shard the CODEBOOK
    rows over the mesh, each device runs the blocked kernel on its slice,
    and a cross-shard argmin combines ``(min, global index)`` with two
    ``lax.pmin`` collectives (ties resolve to the lowest global index, the
    same first-occurrence rule as the reference oracle).

All plans route through ``kernels/ops.vq_assign`` — the serving read path
and the training hot path share one kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.engine.mesh import make_worker_mesh
from repro.kernels import ops

MODES = ("auto", "direct", "shard_batch", "shard_kappa")

# sentinel fill for codebook pad rows in the shard_kappa plan: far enough
# that a padded row can never win the argmin, small enough that ||w||^2
# stays finite in f32 for any practical d (d * 1e30 << 3.4e38)
_PAD_FILL = 1.0e15


class ShardedLookup:
    """Batched nearest-prototype lookup over a 1-D device mesh.

    Parameters
    ----------
    n_devices:     devices to spread the lookup over (default: all).
    mode:          'auto' routes per codebook via the VMEM budget; or force
                   one of 'direct' / 'shard_batch' / 'shard_kappa'.
    budget_bytes:  VMEM budget for the auto routing (None = ops default /
                   ``REPRO_VMEM_BUDGET_BYTES``).
    bm, bk:        kernel block sizes; None (default) defers to the
                   ``kernels.autotune`` roofline pick for each shard shape.
    """

    def __init__(self, n_devices: int | None = None, axis: str = "shards", *,
                 mode: str = "auto", budget_bytes: int | None = None,
                 bm: int | None = None, bk: int | None = None):
        if mode not in MODES:
            raise ValueError(f"unknown lookup mode {mode!r}; "
                             f"choose from {MODES}")
        avail = len(jax.devices())
        self.n_shards = avail if n_devices is None else n_devices
        if not 1 <= self.n_shards <= avail:
            raise ValueError(
                f"need 1 <= n_devices <= {avail}, got {self.n_shards} "
                f"(hint: --xla_force_host_platform_device_count)")
        if mode in ("shard_batch", "shard_kappa") and self.n_shards < 2:
            raise ValueError(f"mode {mode!r} needs >= 2 devices, "
                             f"got {self.n_shards}")
        self.axis = axis
        self.mode = mode
        self.budget_bytes = budget_bytes
        self.bm = bm
        self.bk = bk
        self.mesh = (make_worker_mesh(self.n_shards, axis)
                     if self.n_shards > 1 else None)
        self._compiled: dict[tuple, object] = {}

    # -- planning -----------------------------------------------------------

    def plan(self, kappa: int, d: int) -> str:
        """Which execution plan a (kappa, d) codebook gets."""
        if self.mode != "auto":
            return self.mode
        if self.n_shards == 1:
            return "direct"
        if ops.codebook_fits_vmem(kappa, d, budget_bytes=self.budget_bytes):
            return "shard_batch"
        return "shard_kappa"

    def batch_multiple(self) -> int:
        """Query batches must be padded to a multiple of this row count
        (the micro-batcher's padding target)."""
        return self.n_shards

    # -- execution ----------------------------------------------------------

    def assign(self, z: jax.Array, w: jax.Array
               ) -> tuple[jax.Array, jax.Array]:
        """(batch, d), (kappa, d) -> (assign (batch,) i32, mind (batch,) f32).

        Same contract as ``kernels.ref.vq_assign_ref``; batch must be a
        multiple of ``batch_multiple()`` for the sharded plans.
        """
        z = jnp.asarray(z)
        w = jnp.asarray(w)
        if z.ndim != 2 or w.ndim != 2 or z.shape[1] != w.shape[1]:
            raise ValueError(
                f"want z (batch, d) and w (kappa, d) with matching d, "
                f"got {z.shape} vs {w.shape}")
        plan = self.plan(*w.shape)
        if plan == "direct":
            return ops.vq_assign(z, w, bm=self.bm, bk=self.bk)
        if z.shape[0] % self.n_shards:
            raise ValueError(
                f"batch {z.shape[0]} must be a multiple of "
                f"{self.n_shards} shards for the {plan!r} plan "
                f"(pad the batch — the service's micro-batcher does)")
        if plan == "shard_batch":
            return self._shard_batch(z, w)
        return self._shard_kappa(z, w)

    def _shard_batch(self, z, w):
        key = ("shard_batch", z.shape, w.shape, z.dtype, w.dtype)
        if key not in self._compiled:
            bm, bk = self.bm, self.bk

            def body(z_l, w_l):
                return ops.vq_assign(z_l, w_l, bm=bm, bk=bk)

            self._compiled[key] = jax.jit(jax.shard_map(
                body, mesh=self.mesh, in_specs=(P(self.axis), P()),
                out_specs=(P(self.axis), P(self.axis)),
                axis_names=frozenset({self.axis}), check_vma=False))
        return self._compiled[key](z, w)

    def _shard_kappa(self, z, w):
        kappa = w.shape[0]
        k_local = -(-kappa // self.n_shards)  # ceil
        pad = k_local * self.n_shards - kappa
        if pad:
            # sentinel rows are strictly worse than any real prototype, so
            # they never win the local argmin on the last shard
            w = jnp.concatenate(
                [w, jnp.full((pad, w.shape[1]), _PAD_FILL, w.dtype)])
        key = ("shard_kappa", z.shape, w.shape, z.dtype, w.dtype)
        if key not in self._compiled:
            axis, bm, bk = self.axis, self.bm, self.bk

            def body(z_l, w_l):
                a_l, m_l = ops.vq_assign(z_l, w_l, bm=bm, bk=bk)
                gidx = a_l + jax.lax.axis_index(axis) * w_l.shape[0]
                gmin = jax.lax.pmin(m_l, axis)
                # among shards tied at the global min, the LOWEST global
                # index wins — the oracle's first-occurrence argmin rule
                cand = jnp.where(m_l == gmin, gidx,
                                 jnp.iinfo(jnp.int32).max)
                garg = jax.lax.pmin(cand, axis)
                return garg[None], gmin[None]

            self._compiled[key] = jax.jit(jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(P(), P(self.axis)),
                out_specs=(P(self.axis), P(self.axis)),
                axis_names=frozenset({self.axis}), check_vma=False))
        garg, gmin = self._compiled[key](z, w)
        return garg[0], gmin[0]
