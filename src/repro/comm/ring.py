"""``RingTransport`` — a Pallas ring all-reduce over neighbor RDMA copies.

The XLA collective in ``XlaTransport`` is a black box to the scheduler; a
hand-rolled ring (pallas guide §Ring Collectives) moves the same bytes as
``make_async_remote_copy`` neighbor hops that the latency-hiding scheduler
can overlap with the inner VQ loop — the ROADMAP "TPU-native merge
kernels" item.  The algorithm is the bandwidth-optimal two-phase ring:

  1. **reduce-scatter** — m-1 hops; after hop s, each device has folded its
     left neighbor's partial for chunk ``(my - s - 1) % m`` into its own.
     Device i ends holding the complete sum of chunk ``(i + 1) % m``.
  2. **all-gather**     — m-1 more hops forwarding completed chunks, so
     every device ends with the full summed array.

Per participant that is ``2 * (m-1)/m`` of the payload on the wire — the
same count ``CommRecord`` charges dense transports, so ring and XLA report
identical wire bytes and must produce identical sums.

Off-TPU the transport defaults to the XLA collectives (bit-identical
numerics, same accounting, the records just say ``transport='ring'``).
``RingTransport(use_pallas=True)`` there runs the kernel under Pallas's TPU
interpret mode, which simulates the RDMAs and semaphores on CPU devices:
that is how the tests check the kernel without a chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.comm.api import axis_size
from repro.comm.xla import XlaTransport

_SUBLANE, _LANE = 8, 128   # one f32 vreg tile: each chunk is whole tiles


def _ring_kernel(x_ref, o_ref, buf, send_sem, recv_sem, ready_sem, *,
                 axis: str, m: int):
    """Per-device body under shard_map; x_ref/o_ref are (m, 8, lanes) f32,
    one (8, lanes) chunk per device.

    ``buf[0]`` stages the outgoing chunk and ``buf[1]`` receives the left
    neighbor's.  A neighbor may run hops ahead, so each hop waits for the
    right neighbor's ``ready`` signal (its ``buf[1]`` is read and free)
    before writing into it, and signals its own readiness to the left.
    """
    logical = pltpu.DeviceIdType.LOGICAL
    my = jax.lax.axis_index(axis)
    right = jax.lax.rem(my + 1, m)
    left = jax.lax.rem(my + m - 1, m)

    # neighbor barrier: nobody signals or RDMAs into a peer still outside
    # the kernel, whose scratch semaphores and buffers do not exist yet
    barrier = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(barrier, 1, device_id=left, device_id_type=logical)
    pltpu.semaphore_signal(barrier, 1, device_id=right,
                           device_id_type=logical)
    pltpu.semaphore_wait(barrier, 2)

    o_ref[...] = x_ref[...]

    def hop(send_idx, recv_idx, accumulate: bool):
        """RDMA chunk ``send_idx`` right; fold or store the chunk received
        from the left at ``recv_idx``."""
        buf[0] = o_ref[send_idx]
        pltpu.semaphore_signal(ready_sem, 1, device_id=left,
                               device_id_type=logical)
        pltpu.semaphore_wait(ready_sem, 1)
        rdma = pltpu.make_async_remote_copy(
            src_ref=buf.at[0], dst_ref=buf.at[1],
            send_sem=send_sem, recv_sem=recv_sem,
            device_id=right, device_id_type=logical)
        rdma.start()
        rdma.wait()
        got = buf[1]
        if accumulate:
            got = got + o_ref[recv_idx]
        o_ref[recv_idx] = got

    # phase 1: reduce-scatter — send the running partial for (my - s) % m,
    # fold the left neighbor's partial for (my - s - 1) % m into ours
    for s in range(m - 1):
        hop(jax.lax.rem(my - s + m, m), jax.lax.rem(my - s - 1 + m, m),
            accumulate=True)

    # phase 2: all-gather — forward completed chunks; device i starts with
    # the full sum of chunk (i + 1) % m
    for s in range(m - 1):
        hop(jax.lax.rem(my + 1 - s + m, m), jax.lax.rem(my - s + m, m),
            accumulate=False)


@functools.partial(jax.jit, static_argnames=("axis", "m", "interpret"))
def _ring_pallas(x: jax.Array, *, axis: str, m: int,
                 interpret: bool = False) -> jax.Array:
    return pl.pallas_call(
        functools.partial(_ring_kernel, axis=axis, m=m),
        name="ring_all_reduce",
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, *x.shape[1:]), jnp.float32),  # send / receive
            pltpu.SemaphoreType.DMA,                 # send
            pltpu.SemaphoreType.DMA,                 # recv
            pltpu.SemaphoreType.REGULAR,             # right neighbor ready
        ],
        compiler_params=pltpu.CompilerParams(collective_id=0),
        # TPU interpret mode simulates the RDMAs and semaphores on CPU
        interpret=pltpu.InterpretParams() if interpret else False,
    )(x)


def ring_all_reduce(x: jax.Array, axis: str, *,
                    interpret: bool = False) -> jax.Array:
    """Elementwise f32 sum of ``x`` across ``axis`` via the Pallas ring."""
    m = axis_size(axis)
    flat = x.reshape(-1).astype(jnp.float32)
    if m == 1:
        return flat.reshape(x.shape)
    tile = _SUBLANE * _LANE
    chunk = -(-flat.size // (m * tile)) * tile       # ceil split, whole tiles
    pad = m * chunk - flat.size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    out = _ring_pallas(flat.reshape(m, _SUBLANE, chunk // _SUBLANE),
                       axis=axis, m=m, interpret=interpret)
    return out.reshape(-1)[: x.size].reshape(x.shape)


class RingTransport(XlaTransport):
    """Dense merges over the Pallas ring; XLA fallback off-TPU.

    ``use_pallas=None`` (default) auto-detects: the ring kernel needs real
    inter-chip RDMA, so anything but the TPU backend takes the XLA path.
    ``use_pallas=True`` off-TPU interprets the kernel (slow; for tests).
    Wire accounting is identical either way — the ring moves exactly the
    bytes the dense convention charges.
    """

    name = "ring"

    def __init__(self, use_pallas: bool | None = None):
        super().__init__()
        self.use_pallas = use_pallas

    def _pallas_ok(self) -> bool:
        if self.use_pallas is not None:
            return self.use_pallas
        return jax.default_backend() == "tpu"

    def _ring(self, x: jax.Array, axis: str) -> jax.Array:
        return ring_all_reduce(x, axis,
                               interpret=jax.default_backend() != "tpu")

    def _sum_leaf(self, x: jax.Array, axis: str) -> jax.Array:
        if not self._pallas_ok():
            return super()._sum_leaf(x, axis)
        return self._ring(x, axis)

    def _mean_leaf(self, x: jax.Array, axis: str) -> jax.Array:
        if not self._pallas_ok():
            return super()._mean_leaf(x, axis)
        return (self._ring(x, axis) / axis_size(axis)).astype(x.dtype)
