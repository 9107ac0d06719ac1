"""``QuantizeService`` — batched nearest-prototype lookup as a service.

The serving analogue of the paper's cloud regime: queries arrive one vector
at a time (slow, unpredictable network), but the hardware wants MXU-aligned
batches.  A micro-batching scheduler coalesces incoming requests into one
lookup call — padded to a multiple of ``batch_align=128`` rows — under a
deadline-driven flush:

    submit(z) ──► pending queue ──► flush when EITHER
                                      * coalesced rows >= max_batch, OR
                                      * oldest request age >= max_delay_s
                  ──► pad to batch_align ──► ShardedLookup.assign(batch, w)
                  ──► split results back onto per-request futures

Every flush reads ONE immutable ``CodebookStore`` snapshot, so all rows of
a batch are served by the same ``(version, w)`` pair — a hot-swap mid-batch
can never tear a response — and single-vector requests ride the exact same
``kernels/ops.vq_assign`` hot path as bulk ones.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import Future

import jax
import numpy as np

from repro.obs import NULL_TRACER, Tracer
from repro.serve.codebook_store import CodebookStore
from repro.serve.lookup import ShardedLookup


@dataclasses.dataclass(frozen=True)
class QuantizeRequest:
    """One pending query: ``rows`` vectors awaiting assignment."""

    z: np.ndarray                   # (rows, d) float32
    rows: int
    submitted_at: float             # time.monotonic()
    future: Future = dataclasses.field(repr=False, compare=False,
                                       default_factory=Future)


@dataclasses.dataclass(frozen=True)
class QuantizeResponse:
    """Assignments for one request, stamped with the codebook that served it."""

    assign: np.ndarray              # (rows,) int32 nearest-prototype indices
    mindist: np.ndarray             # (rows,) float32 squared distances
    version: int                    # CodebookStore version served
    latency_s: float                # submit -> response (service-internal)
    queued_s: float                 # submit -> taken into a flush batch
    batch_rows: int                 # real rows of the coalesced flush batch


@dataclasses.dataclass
class ServiceStats:
    """The service's counters, kept by the flush loop (read them after
    ``stop``)."""

    requests: int = 0
    rows: int = 0
    flushes: int = 0
    full_flushes: int = 0           # flushed because max_batch filled up
    deadline_flushes: int = 0       # flushed because the deadline expired
    padded_rows: int = 0            # alignment rows added across all flushes
    failed: int = 0

    @property
    def mean_fill(self) -> float:
        """Mean real rows per flush (how well coalescing worked)."""
        return self.rows / self.flushes if self.flushes else 0.0


class QuantizeService:
    """Deadline-driven micro-batching front end over ``ShardedLookup``.

    Parameters
    ----------
    store:       the ``CodebookStore`` serving reads (hot-swappable).
    lookup:      a ``ShardedLookup`` (default: one over all devices).
    max_batch:   flush as soon as this many rows are pending (default:
                 ``batch_align`` rows per lookup shard — one MXU block per
                 device).
    max_delay_s: flush a partial batch once the oldest pending request has
                 waited this long (the latency bound batching may add).
    batch_align: MXU row alignment for the coalesced batch (NOT a kernel
                 tile size — the lookup's Pallas tiles come from
                 ``kernels.autotune``).
    warmup:      compile the two hot flush shapes (one aligned block and a
                 full ``max_batch``) against the current codebook inside
                 ``start()`` — otherwise the FIRST flush pays the lookup
                 compile and every request queued behind it eats it as
                 latency.
    tracer:      wall spans of the flush thread (``serve.idle_wait``,
                 ``serve.batch_wait``, ``serve.flush`` and its children
                 ``serve.gather``, ``serve.lookup``, ``serve.sync``,
                 ``serve.respond``); they reach a ``jax.profiler`` trace
                 whether or not the tracer records.
    """

    def __init__(self, store: CodebookStore, lookup: ShardedLookup | None = None,
                 *, max_batch: int | None = None, max_delay_s: float = 2e-3,
                 batch_align: int = 128, warmup: bool = True,
                 tracer: Tracer | None = None):
        self.store = store
        self.lookup = lookup if lookup is not None else ShardedLookup()
        if batch_align < 1:
            raise ValueError(f"batch_align must be >= 1, got {batch_align}")
        if batch_align % self.lookup.batch_multiple():
            raise ValueError(
                f"batch_align={batch_align} must be a multiple of the "
                f"lookup's {self.lookup.batch_multiple()} shards so padded "
                f"batches land one aligned block per device")
        self.batch_align = batch_align
        self.max_batch = max_batch if max_batch is not None else (
            batch_align * self.lookup.n_shards)
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if max_delay_s < 0:
            raise ValueError(f"max_delay_s must be >= 0, got {max_delay_s}")
        self.max_delay_s = max_delay_s
        self.warmup = warmup
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.stats = ServiceStats()
        self._cond = threading.Condition()
        self._queue: list[QuantizeRequest] = []
        self._pending_rows = 0
        self._running = False
        self._thread: threading.Thread | None = None

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "QuantizeService":
        with self._cond:
            if self._running:
                raise RuntimeError("service already running")
            self._running = True
        if self.warmup and self.store.version:
            snap = self.store.latest()
            d = snap.w.shape[1]
            align = self.batch_align
            for rows in sorted({align, -(-self.max_batch // align) * align}):
                jax.block_until_ready(self.lookup.assign(
                    np.zeros((rows, d), np.float32), snap.w))
        self._thread = threading.Thread(target=self._flush_loop,
                                        name="quantize-flush", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        """Drain the queue (every accepted request gets a response), then
        stop the flush thread."""
        with self._cond:
            if not self._running:
                return
            self._running = False
            self._cond.notify_all()
        assert self._thread is not None
        self._thread.join()
        self._thread = None

    def __enter__(self) -> "QuantizeService":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- request path -------------------------------------------------------

    def submit(self, z) -> Future:
        """Queue ``z`` ((d,) or (rows, d)); resolves to ``QuantizeResponse``."""
        arr = np.asarray(z, np.float32)
        if arr.ndim == 1:
            arr = arr[None, :]
        if arr.ndim != 2 or arr.shape[0] < 1:
            raise ValueError(f"query must be (d,) or (rows, d), "
                             f"got shape {np.shape(z)}")
        req = QuantizeRequest(z=arr, rows=arr.shape[0],
                              submitted_at=time.monotonic())
        with self._cond:
            if not self._running:
                raise RuntimeError("service is not running (use start() or "
                                   "the context manager)")
            self._queue.append(req)
            self._pending_rows += req.rows
            self._cond.notify_all()
        return req.future

    def quantize(self, z, timeout: float | None = 30.0) -> QuantizeResponse:
        """Synchronous convenience wrapper around ``submit``."""
        return self.submit(z).result(timeout=timeout)

    # -- flush loop ---------------------------------------------------------

    def _take_batch_locked(self) -> tuple[list[QuantizeRequest], bool,
                                          float]:
        """Pop requests up to ``max_batch`` rows (always at least one);
        returns them, whether they fill a batch, and when they were taken
        (``time.monotonic``)."""
        take: list[QuantizeRequest] = [self._queue[0]]
        rows = take[0].rows
        while (len(take) < len(self._queue)
               and rows + self._queue[len(take)].rows <= self.max_batch):
            rows += self._queue[len(take)].rows
            take.append(self._queue[len(take)])
        del self._queue[:len(take)]
        self._pending_rows -= rows
        return take, rows >= self.max_batch, time.monotonic()

    def _flush_loop(self) -> None:
        span = self.tracer.span
        while True:
            with self._cond:
                with span("serve.idle_wait"):
                    while not self._queue and self._running:
                        self._cond.wait()
                if not self._queue:
                    return  # stopped and drained
                deadline = self._queue[0].submitted_at + self.max_delay_s
                with span("serve.batch_wait"):
                    while (self._running
                           and self._pending_rows < self.max_batch):
                        left = deadline - time.monotonic()
                        if left <= 0:
                            break
                        self._cond.wait(left)
                depth = self._pending_rows      # queue depth at flush time
                batch, full, taken_at = self._take_batch_locked()
            self._execute(batch, full, taken_at, depth)

    def _execute(self, batch: list[QuantizeRequest], full: bool,
                 taken_at: float, depth: int) -> None:
        # claim every future first: a client may have cancel()ed while the
        # request was queued, and resolving a cancelled future would raise
        # InvalidStateError and kill the flush thread; once claimed
        # (RUNNING), cancellation can no longer race the set_result below
        batch = [r for r in batch if r.future.set_running_or_notify_cancel()]
        if not batch:
            return
        rows = sum(r.rows for r in batch)
        span = self.tracer.span
        with span("serve.flush", rows=rows, requests=len(batch), full=full,
                  queue_depth=depth):
            try:
                with span("serve.gather"):
                    snap = self.store.latest()
                    z = (batch[0].z if len(batch) == 1
                         else np.concatenate([r.z for r in batch]))
                    pad = (-z.shape[0]) % self.batch_align
                    if pad:
                        z = np.concatenate([z, np.zeros((pad, z.shape[1]),
                                                        np.float32)])
                with span("serve.lookup"):
                    assign, mind = self.lookup.assign(z, snap.w)
                with span("serve.sync"):
                    assign = np.asarray(assign)
                    mind = np.asarray(mind)
            except Exception as e:  # noqa: BLE001 — fault goes to the callers
                for r in batch:
                    r.future.set_exception(e)
                self.stats.failed += len(batch)
                return
            with span("serve.respond"):
                now = time.monotonic()
                off = 0
                for r in batch:
                    r.future.set_result(QuantizeResponse(
                        assign=assign[off:off + r.rows],
                        mindist=mind[off:off + r.rows],
                        version=snap.version,
                        latency_s=now - r.submitted_at,
                        queued_s=taken_at - r.submitted_at,
                        batch_rows=rows))
                    off += r.rows
        self.stats.requests += len(batch)
        self.stats.rows += rows
        self.stats.flushes += 1
        self.stats.padded_rows += pad
        if full:
            self.stats.full_flushes += 1
        else:
            self.stats.deadline_flushes += 1
