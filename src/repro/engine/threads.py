"""``ThreadExecutor`` — the real-thread CloudDALVQ runtime as a backend.

Wraps ``core.async_runtime.run_async_vq`` (worker threads + dedicated
reducer + versioned blob store, no barrier anywhere) behind the Executor
API.  Only the asynchronous delta scheme exists here — threads with a
barrier would just be a slow simulation, so 'average' / 'delta' raise.

Because real threads have no tick clock, ``wall_ticks`` in the returned
``SchemeResult`` holds wall-clock SECONDS (float) instead of ticks.
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.core import async_runtime
from repro.core.schemes import SchemeResult
from repro.engine import api
from repro.obs import NULL_TRACER, MetricsRegistry, Tracer


class ThreadExecutor:
    """Real worker threads + reducer thread (async_delta only)."""

    name = "thread"

    def __init__(self, *, duration_s: float = 2.0, comm_delay_s: float = 0.0,
                 straggler: dict[int, float] | None = None,
                 tracer: Tracer | None = None,
                 metrics: MetricsRegistry | None = None):
        self.duration_s = duration_s
        self.comm_delay_s = comm_delay_s
        self.straggler = straggler
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics

    def run(self, scheme, w0, data, eval_data, *, tau, eps0=0.5, decay=1.0,
            key=None) -> SchemeResult:
        api.validate_scheme(scheme)
        if scheme != "async_delta":
            raise ValueError(
                f"ThreadExecutor only runs 'async_delta' (the thread pool "
                f"has no barrier to express {scheme!r}); use SimExecutor or "
                f"MeshExecutor for the synchronous schemes")
        del eval_data, key  # the runtime evaluates on its own data slice
        t_wall = time.perf_counter()
        with self.tracer.span("engine.run", scheme=scheme,
                              executor=self.name, m=data.shape[0]):
            w, stats, trace = async_runtime.run_async_vq(
                np.asarray(data, np.float32), np.asarray(w0, np.float32),
                tau=tau, duration_s=self.duration_s, eps0=eps0, decay=decay,
                comm_delay_s=self.comm_delay_s, straggler=self.straggler)
        seconds = jnp.asarray([t for t, _ in trace], jnp.float32)
        curve = jnp.asarray([c for _, c in trace], jnp.float32)
        self.last_stats = stats
        wall_s = time.perf_counter() - t_wall
        if self.metrics is not None:
            mt = self.metrics
            mt.histogram("run_wall_s", executor=self.name,
                         scheme=scheme).observe(wall_s)
            h = mt.histogram("distortion", scheme=scheme)
            for _, c in trace:
                h.observe(float(c))
            mt.counter("async_rounds_total", scheme=scheme).inc(
                sum(s.pushes for s in stats))
            mt.counter("stale_reads_total", scheme=scheme).inc(
                sum(s.stale_reads for s in stats))
        if self.tracer.enabled:
            # the thread runtime's trace is (seconds, distortion) pairs —
            # real wall samples, so they land on the wall timeline in us
            for t, c in trace:
                self.tracer.counter("distortion", float(c), ts_us=t * 1e6,
                                    process=self.tracer.WALL_PROCESS)
        return SchemeResult(w_shared=jnp.asarray(w), wall_ticks=seconds,
                            distortion=curve)
