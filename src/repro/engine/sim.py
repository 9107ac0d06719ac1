"""``SimExecutor`` — the single-device simulations behind the Executor API.

Wraps ``core.schemes.scheme_average`` / ``scheme_delta`` (vmap over the
worker axis on one chip) and ``core.async_vq.scheme_async`` (tick-by-tick
eq.-9 simulation).  These are the numerical ORACLES the mesh backend is
tested against; the executor only adapts signatures and threads the
``NetworkModel`` draw into the async simulation.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from repro.core import async_vq, schemes
from repro.core.schemes import SchemeResult
from repro.engine import api
from repro.engine.network import GeometricDelayNetwork, NetworkModel
from repro.obs import NULL_TRACER, MetricsRegistry, Tracer


class SimExecutor:
    """Single-device oracle backend (jit/vmap simulation of M workers)."""

    name = "sim"

    def __init__(self, network: NetworkModel | None = None,
                 eval_every: int = 10, *, tracer: Tracer | None = None,
                 metrics: MetricsRegistry | None = None):
        self.network = network or GeometricDelayNetwork()
        self.eval_every = eval_every
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics

    def run(self, scheme: str, w0: jax.Array, data: jax.Array,
            eval_data: jax.Array, *, tau: int, eps0: float = 0.5,
            decay: float = 1.0, key: jax.Array | None = None) -> SchemeResult:
        api.validate_scheme(scheme)
        t_wall = time.perf_counter()
        with self.tracer.span("engine.run", scheme=scheme,
                              executor=self.name, m=data.shape[0]):
            if scheme in ("average", "delta"):
                fn = (schemes.scheme_average if scheme == "average"
                      else schemes.scheme_delta)
                res = fn(w0, data, eval_data, tau=tau, eps0=eps0, decay=decay)
                # the oracles assume instant communications (ticks = k*tau);
                # restate wall time under this executor's NetworkModel so sim
                # and mesh curves share a time axis for any network
                wt = self.network.window_ticks(tau)
                if wt != tau:
                    res = SchemeResult(w_shared=res.w_shared,
                                       wall_ticks=(res.wall_ticks // tau) * wt,
                                       distortion=res.distortion)
            else:
                key = jax.random.PRNGKey(0) if key is None else key
                m, n, _ = data.shape
                lengths = self.network.round_lengths(key, m, n // tau + 2, tau)
                r = async_vq.scheme_async(w0, data, eval_data, key, tau=tau,
                                          eps0=eps0, decay=decay,
                                          eval_every=self.eval_every,
                                          lengths=lengths)
                res = SchemeResult(w_shared=r.w_shared,
                                   wall_ticks=r.wall_ticks,
                                   distortion=r.distortion)
        self._emit_obs(scheme, res, time.perf_counter() - t_wall)
        return res

    def _emit_obs(self, scheme: str, res: SchemeResult,
                  wall_s: float) -> None:
        """Distortion-over-ticks counters on one ``sim`` timeline track."""
        tr, mt = self.tracer, self.metrics
        if mt is not None:
            mt.histogram("run_wall_s", executor=self.name,
                         scheme=scheme).observe(wall_s)
            h = mt.histogram("distortion", scheme=scheme)
            for c in np.asarray(res.distortion):
                h.observe(float(c))
        if tr.enabled:
            ticks = np.asarray(res.wall_ticks)
            curve = np.asarray(res.distortion)
            for t, c in zip(ticks, curve):
                tr.counter("distortion", float(c), ts_us=float(t))
