"""``MeshExecutor`` — the paper's schemes on a REAL JAX device mesh.

One worker per device: worker streams are sharded over the worker axes
with shard_map, each device runs its own sequential-VQ inner loop, and the
reducing phases are collectives issued through the pluggable ``repro.comm``
transport layer.  The mesh comes from a ``repro.topology.Topology`` — a
flat topology (the default) is the classic 1-D ``workers`` axis; a
hierarchical one (``topology=Topology.from_spec(8, hosts=2)``) builds the
2-D ``(hosts, workers)`` grid, the scans shard and reduce over the joint
axes, and a ``HierarchicalTransport`` splits each merge into a dense
intra-host tier and a (typically sparse) inter-host tier with per-tier
wire accounting.  The schemes —

  * average  (eq. 3): cross-worker mean of the worker versions;
  * delta    (eq. 8): cross-worker sum of the worker displacements;
  * async    (eq. 9): a per-tick MASKED sum — only workers whose
    communication round (drawn from the pluggable ``NetworkModel``)
    completes at this tick contribute their in-flight delta, which is the
    barrier-free reducer of the paper's cloud architecture expressed as an
    SPMD collective (``Transport.masked_all_reduce``).

Which wire the merge rides is the executor's ``transport``: dense XLA
(default, the numerics oracle), the Pallas ring, or top-k sparse — and
every collective appends a ``CommRecord``, so ``last_comm`` reports the
bytes the run actually moved (records traced per compiled program are
replayed on compile-cache hits).

The per-worker inner loop runs on Pallas kernels (interpret mode on
CPU): a window whose codebook fits the VMEM budget is one ``vq_window``
dispatch; a larger one takes one ``vq_assign`` dispatch a step against
carried row norms (the whole codebook as one block while it fits, the
blocked grid past it) and updates only the winning row — the same
larger-than-VMEM routing as the serving lookup.

On CPU, force a mesh with ``--xla_force_host_platform_device_count=8`` (set
before jax initializes; see tests/conftest.py) — the SPMD program is then
bit-for-bit the one a real 8-chip mesh runs.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro import comm
from repro.core import vq
from repro.core.schemes import SchemeResult
from repro.engine import api, merge as merge_lib
from repro.engine.network import GeometricDelayNetwork, NetworkModel
from repro.kernels import ops
from repro.obs import NULL_TRACER, MetricsRegistry, Tracer
from repro.topology import Topology
from repro.topology import make_worker_mesh  # noqa: F401 — re-export; the
# construction itself lives in repro.topology (the only module allowed to
# build meshes — CI-pinned)


# Names of the device work inside a compiled segment: each scope lands in
# its ops' op_name metadata, which a profiler trace shows as the op's
# ``tf_op`` path, so the ops are found by name after any refactor.
LOCAL_WINDOW_SCOPE = "local_window"
MERGE_SCOPE = "merge"
EVAL_PROBE_SCOPE = "eval_probe"


def _validate_axis_names(mesh: Mesh, axes: tuple[str, ...]) -> None:
    if any(not name for name in mesh.axis_names):
        raise ValueError(
            f"mesh axis names must be non-empty, got {mesh.axis_names}")
    for axis in axes:
        if axis not in mesh.axis_names:
            raise ValueError(
                f"worker axis {axis!r} not in mesh axes {mesh.axis_names}")


def _validate_mesh(mesh: Mesh, axes: tuple[str, ...], m: int) -> None:
    _validate_axis_names(mesh, axes)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    have = 1
    for axis in axes:
        have *= sizes[axis]
    if have != m:
        raise ValueError(
            f"data has M={m} worker streams but mesh axes {axes!r} have "
            f"{have} devices — one worker per device is required")


def _local_window(w0: jax.Array, zwin: jax.Array, t0: jax.Array, *,
                  eps0: float, decay: float, use_pallas: bool,
                  vmem_budget: int | None = None
                  ) -> tuple[jax.Array, jax.Array]:
    """tau sequential VQ steps (eq. 1) on one device; returns (delta, w).

    A product quantizer's (m, k, d/m) codebook takes each step in all m
    sub-spaces at once: the ``pq_window`` kernel, or a scan of
    ``vq.pq_H`` without Pallas."""
    tau = zwin.shape[0]
    if w0.ndim == 3:
        eps = vq.default_steps(t0 + 1 + jnp.arange(tau, dtype=jnp.int32),
                               eps0=eps0, decay=decay)
        if use_pallas:
            w = ops.pq_window(zwin, w0, eps)
        else:
            w, _ = jax.lax.scan(lambda w, x: (w - x[1] * vq.pq_H(x[0], w),
                                              None), w0, (zwin, eps))
        return w0 - w, w
    if _window_routed(w0.shape, tau, use_pallas, vmem_budget):
        # whole window in ONE Pallas dispatch: tau steps with the codebook
        # VMEM-resident, eliminating tau-1 per-step kernel launches — the
        # step schedule is precomputed (it depends only on t0) and the
        # kernel replays the per-step float ops exactly, so this path is
        # bit-identical to ``_step_window`` (tests/test_step_route.py)
        eps = vq.default_steps(t0 + 1 + jnp.arange(tau, dtype=jnp.int32),
                               eps0=eps0, decay=decay)
        w = ops.vq_window(zwin, w0, eps)
        return w0 - w, w

    if not use_pallas:
        def body(carry, z):
            w, t = carry
            eps = vq.default_steps(t + 1, eps0=eps0, decay=decay)
            return (w - eps * vq.H(z, w), t + 1), None

        (w, _), _ = jax.lax.scan(body, (w0, t0), zwin)
        return w0 - w, w

    w, _ = _step_window(w0, zwin, t0, eps0=eps0, decay=decay,
                        vmem_budget=vmem_budget)
    return w0 - w, w


def _window_routed(shape: tuple, tau: int, use_pallas: bool,
                   vmem_budget: int | None) -> bool:
    """Does ``_local_window`` run a (kappa, d) codebook through the
    ``vq_window`` kernel?"""
    return (use_pallas and len(shape) == 2
            and ops.window_fits_vmem(*shape, tau, budget_bytes=vmem_budget))


def _step_window(w0: jax.Array, zwin: jax.Array, t0: jax.Array, *,
                 eps0: float, decay: float, vmem_budget: int | None = None
                 ) -> tuple[jax.Array, jax.Array]:
    """tau sequential VQ steps, one assign-kernel dispatch each; returns
    (w, the rows' squared norms (1, kappa)).

    The window kernel's step, taken one dispatch at a time: the norms are
    computed once on entry and carried, the assign kernel finds the
    winner against them, and only the winning row and its norm change
    (eq. 4's H is zero on every other row).  Nothing of (kappa, d) size
    is formed per step beyond the distance product."""

    def step(carry, z):
        w, w2, t = carry
        eps = vq.default_steps(t + 1, eps0=eps0, decay=decay)
        arg, _ = ops.vq_assign_routed(z[None, :], w, w2=w2,
                                      budget_bytes=vmem_budget)
        k = arg[0]
        row = jax.lax.dynamic_slice_in_dim(w, k, 1)              # (1, d)
        h = row - z[None, :]
        row = row - eps * h
        w = jax.lax.dynamic_update_slice_in_dim(w, row, k, axis=0)
        w2 = jax.lax.dynamic_update_slice_in_dim(
            w2, jnp.sum(row * row, axis=1, keepdims=True), k, axis=1)
        return (w, w2, t + 1), None

    w2 = jnp.sum(w0 * w0, axis=1)[None, :]
    (w, w2, _), _ = jax.lax.scan(step, (w0, w2, t0), zwin)
    return w, w2


class MeshExecutor:
    """One worker per mesh device, merged with collectives (the headline)."""

    name = "mesh"

    def __init__(self, mesh: Mesh | None = None, axis: str = "workers",
                 network: NetworkModel | None = None, *,
                 topology: Topology | None = None,
                 transport: comm.Transport | str | None = None,
                 use_pallas: bool = True,
                 eval_every: int = 10,
                 vmem_budget_bytes: int | None = None,
                 on_window: Callable[[int, jax.Array], None] | None = None,
                 publish_every: int = 1,
                 merge: str | None = None, quorum_frac: float = 0.6,
                 staleness_gamma: float = 0.5,
                 divergence_thresh: float = 0.0, max_stale: int = 8,
                 tier1_controller=None,
                 tracer: Tracer | None = None,
                 metrics: MetricsRegistry | None = None,
                 profiler=None):
        if not axis:
            raise ValueError("worker axis name must be a non-empty string")
        if merge not in (None, "quorum", "dynamic"):
            raise ValueError(
                f"merge override must be None (scheme default), 'quorum', "
                f"or 'dynamic', got {merge!r}")
        if not 0.0 < quorum_frac <= 1.0:
            raise ValueError(
                f"quorum_frac must be in (0, 1], got {quorum_frac}")
        if divergence_thresh < 0.0:
            raise ValueError(
                f"divergence_thresh must be >= 0, got {divergence_thresh}")
        if max_stale < 1:
            raise ValueError(f"max_stale must be >= 1, got {max_stale}")
        if topology is not None:
            if mesh is not None:
                raise ValueError(
                    "pass mesh= or topology=, not both — a topology builds "
                    "its own mesh")
            # the topology owns the axis model: a flat topology is the 1-D
            # worker mesh (bit-identical to the pre-topology path), a
            # hierarchical one the 2-D (hosts, workers) grid
            axis = topology.worker_axis
            mesh = topology.make_mesh()
        if mesh is not None:
            _validate_axis_names(
                mesh, topology.axes if topology is not None else (axis,))
        if publish_every < 1:
            raise ValueError(f"publish_every must be >= 1, "
                             f"got {publish_every}")
        self.mesh = mesh
        self.axis = axis
        self.topology = topology
        self.network = network or GeometricDelayNetwork()
        self.transport = comm.get_transport(
            transport if transport is not None else "xla")
        self.use_pallas = use_pallas
        self.eval_every = eval_every
        self.vmem_budget_bytes = vmem_budget_bytes
        # merge override: None = the scheme's own strategy (the default,
        # byte-identical program); "quorum" = straggler-tolerant eq. 8
        # (delta scheme only), proceeding on ceil(quorum_frac * M) arrivals
        # and folding late deltas via the stale-window rule; "dynamic" =
        # divergence-triggered eq. 8 (delta scheme only): merge when the
        # probed global drift crosses divergence_thresh or max_stale
        # windows have passed, re-pricing the traced merge wire to the
        # measured trigger count after each run
        self.merge = merge
        self.quorum_frac = quorum_frac
        self.staleness_gamma = staleness_gamma
        self.divergence_thresh = divergence_thresh
        self.max_stale = max_stale
        # bandwidth-adaptive sparse tier: a Tier1BudgetController re-sizes
        # the transport's tier1_frac after every published chunk from the
        # chunk's measured tier-1 wire bytes (engine.network closes the
        # loop the CommLog/transfer_ticks accounting opened); setting it
        # routes sync runs through the chunked publish path even without
        # an on_window hook, since frac is trace-static and can only
        # change at a program boundary
        self.tier1_controller = tier1_controller
        # publication hook: when set, the sync schemes run in host-level
        # chunks of ``publish_every`` windows (numerically identical — the
        # window scan is sequential either way) and ``on_window(windows_done,
        # w_shared)`` fires after each chunk's merge; a CodebookStore's
        # ``publisher()`` plugs in here to hot-swap a live serving codebook.
        # The async scheme has no window barrier: it publishes once, at end.
        self.on_window = on_window
        self.publish_every = publish_every
        # observability: a disabled tracer records nothing (its wall spans
        # still open profiler annotations) and compiles the bare program;
        # when a registry is attached every CommRecord is mirrored onto it
        # (per-tag/per-tier wire bytes become first-class metrics)
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics
        if metrics is not None:
            self.transport.log.attach_metrics(metrics)
        # roofline attribution (obs.profile.Profiler): when attached, compile
        # misses go through the AOT path (lower -> compile -> run) so the
        # profiler parses the HLO of the very executable that runs — zero
        # extra compiles, and the cached callable is the compiled object
        self.profiler = profiler
        # compiled-program cache: rebuilding the shard_map closure on every
        # run() would recompile each time; key = everything trace-affecting.
        # Each entry also keeps the CommRecords traced for that program, so
        # cache hits replay the accounting the trace measured.
        self._compiled: dict[tuple, tuple] = {}
        # comm summary of the most recent run()/run_segment() (CommLog dict)
        self.last_comm: dict | None = None

    # -- topology-derived axis model ----------------------------------------

    @property
    def _axes(self) -> tuple[str, ...]:
        """Mesh axes the worker dimension shards over, outermost first."""
        if self.topology is not None:
            return self.topology.axes
        return (self.axis,)

    @property
    def _spec(self):
        """PartitionSpec entry / reduce-axis spec for the worker dim: the
        bare axis name on a flat mesh, the (hosts, workers) tuple on a
        hierarchical one (transports and strategies take either)."""
        if self.topology is not None:
            return self.topology.spec
        return self.axis

    @property
    def _topology_label(self) -> str:
        """Human label for attribution records: 'flat' or 'HxW'."""
        if self.topology is not None:
            return self.topology.describe()
        return "flat"

    # -- comm-aware compile cache -------------------------------------------

    def _call_compiled(self, cache_key: tuple, build: Callable, *args):
        """Run the cached program for ``cache_key`` (building+tracing it on
        a miss), replaying its traced ``CommRecord``s on every hit."""
        log = self.transport.log
        if cache_key not in self._compiled:
            fn = build()
            mark = log.mark()
            with self.tracer.span("engine.compile", program=str(cache_key[0])):
                if self.profiler is not None:
                    # AOT split: .lower() runs the Python trace (appending
                    # the CommRecords exactly once), .compile() yields the
                    # post-SPMD HLO + cost_analysis, and the compiled
                    # executable is cached as the callable — same program,
                    # same numerics, no second compile
                    compiled = fn.lower(*args).compile()
                    try:
                        cost = compiled.cost_analysis()
                    except NotImplementedError:   # backend has no estimate
                        cost = None
                    self.profiler.record_program(
                        cache_key, compiled.as_text(), cost)
                    fn = compiled
                out = fn(*args)              # first call traces -> records
            self._compiled[cache_key] = (fn, log.since(mark))
            return out
        fn, records = self._compiled[cache_key]
        log.extend(records)
        return fn(*args)

    def _merge_wire_by_tier(self, cache_key: tuple) -> dict:
        """Merge-tag wire bytes one execution of ``cache_key`` moves per
        participant, grouped by tier (None = untiered flat traffic, 0 =
        intra-host, 1 = inter-host) for the network model's per-link-class
        bandwidth charge."""
        _, records = self._compiled[cache_key]
        out: dict = {}
        for r in records:
            if r.tag == "merge":
                out[r.tier] = out.get(r.tier, 0) + r.wire_bytes * r.calls
        return out

    # -- public API ---------------------------------------------------------

    def _check_codebook(self, scheme: str, w0, data) -> None:
        """A (kappa, d) codebook runs every scheme and transport.  A product
        quantizer's (m, k, d/m) sub-codebooks run the synchronous schemes
        over the XLA transport, whose merges are elementwise."""
        if w0.ndim == 2:
            return
        if w0.ndim != 3 or w0.shape[0] * w0.shape[2] != data.shape[-1]:
            raise ValueError(
                f"codebook must be (kappa, d) or (m, k, d/m) sub-codebooks "
                f"of a product quantizer, got {w0.shape} for d="
                f"{data.shape[-1]}")
        if (scheme not in ("average", "delta")
                or self.transport.name != "xla" or self.merge is not None):
            raise ValueError(
                f"a product quantizer trains with scheme 'average' or "
                f"'delta' over the 'xla' transport and no merge override; "
                f"got scheme {scheme!r}, transport "
                f"{self.transport.name!r}, merge {self.merge!r}")

    def run(self, scheme: str, w0: jax.Array, data: jax.Array,
            eval_data: jax.Array, *, tau: int, eps0: float = 0.5,
            decay: float = 1.0, key: jax.Array | None = None) -> SchemeResult:
        api.validate_scheme(scheme)
        if data.ndim != 3:
            raise ValueError(f"data must be (M, n, d), got {data.shape}")
        if eval_data.ndim != 3 or eval_data.shape[0] != data.shape[0]:
            raise ValueError(
                f"eval_data must be (M, n_eval, d) with the same M as data; "
                f"got {eval_data.shape} vs M={data.shape[0]}")
        self._check_codebook(scheme, w0, data)
        m = data.shape[0]
        mesh = self.mesh if self.mesh is not None else make_worker_mesh(
            m, self.axis)
        _validate_mesh(mesh, self._axes, m)
        mark = self.transport.log.mark()
        t_wall = time.perf_counter()
        try:
            with self.tracer.span("engine.run", scheme=scheme,
                                  executor=self.name, m=m,
                                  transport=self.transport.name):
                if scheme == "async_delta":
                    res = self._run_async(mesh, w0, data, eval_data, tau=tau,
                                          eps0=eps0, decay=decay, key=key)
                    if self.on_window is not None:
                        self.on_window(data.shape[1] // tau, res.w_shared)
                elif (self.on_window is not None
                      or self.tier1_controller is not None):
                    res = self._run_sync_published(mesh, scheme, w0, data,
                                                   eval_data, tau=tau,
                                                   eps0=eps0, decay=decay,
                                                   t0=0)
                else:
                    res, _ = self._run_sync(mesh, scheme, w0, data, eval_data,
                                            tau=tau, eps0=eps0, decay=decay)
        finally:
            self.last_comm = comm.CommLog.summarize(
                self.transport.log.since(mark))
        wall_s = time.perf_counter() - t_wall
        if self.metrics is not None:
            self.metrics.histogram("run_wall_s", executor=self.name,
                                   scheme=scheme).observe(wall_s)
        if self.profiler is not None:
            self.profiler.finish_run(wall_s)
        return res

    def run_segment(self, scheme: str, w0: jax.Array, data: jax.Array,
                    eval_data: jax.Array, *, tau: int, eps0: float = 0.5,
                    decay: float = 1.0, t0: int = 0,
                    mesh: Mesh | None = None) -> SchemeResult:
        """One elastic segment: sync windows starting at local step ``t0``.

        The ``ElasticMeshExecutor`` hook — identical to ``run`` for the
        synchronous schemes except that the Robbins-Monro step schedule
        continues from ``t0`` (so a resized run keeps the same eps_t sequence
        a fixed-M run would see) and the caller may supply the mesh built by
        ``distributed.elastic.plan_remesh`` for the current worker set."""
        api.validate_scheme(scheme)
        if scheme == "async_delta":
            raise ValueError(
                "elastic segments support the synchronous schemes "
                "('average', 'delta'); async_delta has no window barrier "
                "to resize at")
        if data.ndim != 3:
            raise ValueError(f"data must be (M, n, d), got {data.shape}")
        self._check_codebook(scheme, w0, data)
        m = data.shape[0]
        if mesh is None:
            mesh = self.mesh if self.mesh is not None else make_worker_mesh(
                m, self.axis)
        _validate_mesh(mesh, self._axes, m)
        mark = self.transport.log.mark()
        try:
            with self.tracer.span("engine.segment", scheme=scheme, m=m, t0=t0):
                if (self.on_window is not None
                        or self.tier1_controller is not None):
                    res = self._run_sync_published(mesh, scheme, w0, data,
                                                   eval_data, tau=tau,
                                                   eps0=eps0, decay=decay,
                                                   t0=t0)
                else:
                    res, _ = self._run_sync(mesh, scheme, w0, data, eval_data,
                                            tau=tau, eps0=eps0, decay=decay,
                                            t0=t0)
        finally:
            self.last_comm = comm.CommLog.summarize(
                self.transport.log.since(mark))
        return res

    # -- synchronous schemes (eqs. 3 and 8) ---------------------------------

    def _run_sync_published(self, mesh: Mesh, scheme: str, w0, data,
                            eval_data, *, tau: int, eps0: float, decay: float,
                            t0: int) -> SchemeResult:
        """``_run_sync`` in host-level chunks of ``publish_every`` windows,
        firing ``on_window`` after each chunk — same numerics (the window
        scan is sequential, and the merge/transport state threads across
        chunks exactly as it threads across the scan), at most two extra
        compiled programs (the chunk shape and one remainder shape).

        The drain is DOUBLE-BUFFERED: chunk k+1 is dispatched before
        chunk k's host-side reads (``np.asarray`` on the curve, the tick
        conversion, the ``on_window`` publish) block on its result — the
        latency-hiding pattern ``comm/ring.py`` uses for neighbor hops,
        lifted to the host loop, so the merge collective at the tail of
        one chunk overlaps the next chunk's compute.  The same
        programs run in the same order with the same inputs (chunk k+1
        depends on chunk k only through device arrays), so the pipelining
        is bit-stable; ``on_window`` still fires in chunk order."""
        n_windows = data.shape[1] // tau
        w, t, done = w0, t0, 0
        curves, ticks = [], []
        wt, ms = None, None
        pending = None          # (result, windows done BEFORE its chunk)

        def drain(slot, wt):
            res, base = slot
            if wt is None:
                # per-window tick cost as the segment run charged it
                # (window_ticks + any bandwidth transfer charge)
                wt = int(res.wall_ticks[0])
            curves.append(np.asarray(res.distortion))
            ticks.append(base * wt + np.asarray(res.wall_ticks))
            if self.on_window is not None:
                self.on_window(base + res.wall_ticks.shape[0], res.w_shared)
            return wt

        while done < n_windows:
            k = min(self.publish_every, n_windows - done)
            seg = data[:, done * tau:(done + k) * tau]
            cmark = self.transport.log.mark()
            with self.tracer.span("engine.chunk", windows=k, t0=t):
                res, ms = self._run_sync(mesh, scheme, w, seg, eval_data,
                                         tau=tau, eps0=eps0, decay=decay,
                                         t0=t, merge_state=ms)
            w = res.w_shared     # device-side dependency only: no host sync
            if pending is not None:
                wt = drain(pending, wt)
            pending = (res, done)
            done += k
            t += k * tau
            if self.tier1_controller is not None:
                self._adapt_tier1(cmark, n_windows_chunk=k, t_ticks=t)
        if pending is not None:
            wt = drain(pending, wt)
        if not curves:
            raise ValueError(
                f"need at least one tau={tau} window, got n={data.shape[1]}")
        return SchemeResult(
            w_shared=w,
            wall_ticks=jnp.asarray(np.concatenate(ticks), jnp.int32),
            distortion=jnp.asarray(np.concatenate(curves)))

    def _adapt_tier1(self, cmark: int, *, n_windows_chunk: int,
                     t_ticks: int) -> None:
        """One bandwidth-control step: feed the chunk's measured tier-1
        merge wire (bytes per window) to the ``Tier1BudgetController``,
        which re-sizes the transport's sparse fraction in place.  The new
        frac enters the next chunk's compile-cache key, so the program set
        stays bounded by the controller's ladder."""
        recs = self.transport.log.since(cmark)
        wire1 = sum(r.wire_bytes * r.calls for r in recs
                    if r.tag in ("merge", "probe") and r.tier == 1)
        frac = self.tier1_controller.update(
            self.transport, wire1 / max(n_windows_chunk, 1))
        if frac is None:
            return
        if self.metrics is not None:
            self.metrics.gauge("tier1_frac").set(frac)
        if self.tracer.enabled:
            self.tracer.counter("tier1_frac", float(frac),
                                ts_us=float(t_ticks))

    def _transport_frac_key(self) -> tuple:
        """Compile-cache fingerprint of the transport's trace-affecting
        compression knobs: the adaptive controller mutates ``frac`` (a
        static top-k shape) between chunks, so a cached program must be
        keyed on the value it was traced with.  A ``QuantizedTransport``
        is transparent here (the knobs live on its inner transport)."""
        t = self.transport
        t = getattr(t, "inner", t)
        return (getattr(t, "tier1_frac", None), getattr(t, "frac", None))

    def _run_sync(self, mesh: Mesh, scheme: str, w0, data, eval_data, *,
                  tau: int, eps0: float, decay: float, t0: int = 0,
                  merge_state=None) -> tuple[SchemeResult, Any]:
        """One compiled sync segment.  Returns ``(result, merge_state)`` so
        host-chunked callers (the publish path) can thread stateful-merge
        state — e.g. the sparse transport's error-feedback residual —
        across chunks instead of resetting it per program.  The host-side
        state representation carries a leading (M, ...) worker dim (the
        state is per-worker distinct, sharded over the axis)."""
        axis = self._spec
        axes = self._axes
        m = data.shape[0]
        n = data.shape[1]
        n_windows = n // tau
        quorum = self.merge == "quorum"
        dynamic = self.merge == "dynamic"
        late_np = None
        if quorum:
            if scheme != "delta":
                raise ValueError(
                    "the quorum merge folds eq.-8 displacements, so it rides "
                    f"scheme 'delta' only; got scheme {scheme!r}")
            strategy = merge_lib.get_merge(
                "quorum", transport=self.transport,
                quorum_frac=self.quorum_frac, gamma=self.staleness_gamma)
            # host-side lateness schedule: (m, n_windows) arrival-miss bits
            # drawn from the network model (and any chaos schedule wrapping
            # it), keyed by GLOBAL window so elastic segments stay aligned
            late_np = np.asarray(
                self.network.late_matrix(m, n_windows, tau,
                                         window0=t0 // tau), np.float32)
        elif dynamic:
            if scheme != "delta":
                raise ValueError(
                    "the dynamic merge folds eq.-8 displacements, so it "
                    f"rides scheme 'delta' only; got scheme {scheme!r}")
            strategy = merge_lib.get_merge(
                "dynamic", transport=self.transport,
                thresh=self.divergence_thresh, gamma=self.staleness_gamma,
                max_stale=self.max_stale)
        else:
            strategy = merge_lib.get_merge(scheme, transport=self.transport)
        transport = self.transport
        use_pallas = self.use_pallas
        vmem_budget = self.vmem_budget_bytes
        probe = vq.pq_distortion if w0.ndim == 3 else vq.distortion
        if merge_state is None:
            # host-side merge state carries a leading per-worker dim: the
            # state (e.g. the sparse error-feedback residual) is DISTINCT
            # per worker, so it crosses the program boundary sharded over
            # the axis — not as a nominally-replicated array whose device
            # buffers secretly disagree
            merge_state = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (m,) + x.shape),
                strategy.init_state(w0))

        # observing runs additionally reduce the inter-worker codebook
        # divergence each window (mean over workers of ||w_local - w_merged||^2
        # — the future DynamicMerge trigger signal); the reduce rides an
        # "eval"-tagged collective so the exactly-pinned merge wire bytes are
        # untouched, and the flag joins the cache key because it changes the
        # compiled program's outputs.  A profiler rides the SAME fork — no
        # additional program variant beyond observe
        observe = (self.tracer.enabled or self.metrics is not None
                   or self.profiler is not None)

        def body(w0_in, t0_in, ms_in, data_l, eval_l, *late_in):
            stream = data_l[0]                       # (n, d) local shard
            windows = stream[: n_windows * tau].reshape(n_windows, tau, -1)
            ev = eval_l[0]                           # (n_eval, d)
            ms0 = jax.tree.map(lambda x: x[0], ms_in)  # drop worker dim
            xs = (windows, late_in[0][0]) if quorum else (windows,)

            def window(carry, x):
                zwin = x[0]
                w_srd, t, ms = carry
                with jax.named_scope(LOCAL_WINDOW_SCOPE):
                    _, w_fin = _local_window(
                        w_srd, zwin, t, eps0=eps0, decay=decay,
                        use_pallas=use_pallas, vmem_budget=vmem_budget)
                late = {"late": x[1]} if quorum else {}
                with jax.named_scope(MERGE_SCOPE):
                    w_srd, ms = strategy(w_srd, w_fin, axis, ms,
                                         calls=n_windows, **late)
                # the dynamic merge's per-window sync decision, stacked into
                # a program output so the host can re-price the wire and tag
                # the trace with what actually triggered
                extra = (strategy.last_trigger,) if dynamic else ()
                t = t + tau
                if observe:
                    # one stacked reduce for (distortion, divergence): the
                    # observing program keeps the bare program's collective
                    # count, so live instrumentation stays on the <3% obs
                    # bench budget
                    with jax.named_scope(EVAL_PROBE_SCOPE):
                        cd, _ = transport.all_reduce(
                            jnp.stack([probe(ev, w_srd),
                                       jnp.sum((w_fin - w_srd) ** 2)]),
                            axis, op="mean", calls=n_windows, tag="eval")
                    return (w_srd, t, ms), (cd[0], cd[1]) + extra
                with jax.named_scope(EVAL_PROBE_SCOPE):
                    c, _ = transport.all_reduce(
                        probe(ev, w_srd), axis, op="mean",
                        calls=n_windows, tag="eval")
                return (w_srd, t, ms), ((c,) + extra if dynamic else c)

            (w_srd, _, ms_out), ys = jax.lax.scan(
                window, (w0_in, t0_in, ms0), xs)
            ms_out = jax.tree.map(lambda x: x[None], ms_out)
            if observe and dynamic:
                return w_srd, ys[0], ys[1], ys[2], ms_out
            if observe:
                return w_srd, ys[0], ys[1], ms_out
            if dynamic:
                return w_srd, ys[0], ys[1], ms_out
            return w_srd, ys, ms_out

        cache_key = ("sync", scheme, mesh, w0.shape, data.shape,
                     eval_data.shape, tau, eps0, decay, use_pallas,
                     vmem_budget, observe, self._transport_frac_key())
        if quorum:
            cache_key += ("quorum", self.quorum_frac, self.staleness_gamma)
        if dynamic:
            cache_key += ("dynamic", self.divergence_thresh,
                          self.staleness_gamma, self.max_stale)

        def build():
            # replicated outputs: w_shared + curve (+ divergence when
            # observing, + trigger bits when dynamic), then the sharded
            # merge state
            n_rep = 2 + (1 if observe else 0) + (1 if dynamic else 0)
            out_specs = tuple(P() for _ in range(n_rep)) + (P(axis),)
            in_specs = (P(), P(), P(axis), P(axis), P(axis))
            if quorum:
                in_specs += (P(axis),)
            return jax.jit(jax.shard_map(
                body, mesh=mesh,
                in_specs=in_specs,
                out_specs=out_specs,
                axis_names=frozenset(axes), check_vma=False))

        args = (w0, jnp.asarray(t0, jnp.int32), merge_state, data, eval_data)
        if quorum:
            args += (jnp.asarray(late_np),)
        freshly_compiled = cache_key not in self._compiled
        mark2 = self.transport.log.mark()
        out = self._call_compiled(cache_key, build, *args)
        if self.profiler is not None:
            self.profiler.note_segment(
                program=cache_key, scheme=scheme,
                transport=self.transport.name, topology=self._topology_label,
                m=m, n_windows=n_windows, d=data.shape[-1],
                kappa=w0.shape[-2],
                subspaces=w0.shape[0] if w0.ndim == 3 else 1,
                tau=tau, n_eval=eval_data.shape[1],
                compiled=freshly_compiled,
                window_body=(ops.window_body() if _window_routed(
                    w0.shape, tau, use_pallas, vmem_budget) else None))
        trig = None
        if observe and dynamic:
            w_final, curve, divergence, trig, ms_out = out
        elif observe:
            w_final, curve, divergence, ms_out = out
        elif dynamic:
            (w_final, curve, trig, ms_out), divergence = out, None
        else:
            (w_final, curve, ms_out), divergence = out, None
        trig_np = None
        if dynamic:
            # honest wire accounting: SPMD can't skip a collective at trace
            # time, so the traced merge records claim every window synced;
            # re-price them to the windows that actually TRIGGERED (the
            # probe stays at full calls — its psum runs every window)
            trig_np = np.asarray(trig)
            n_trig = int(trig_np.sum())

            def _reprice(r):
                if r.tag != "merge" or r.calls == n_trig:
                    return r
                if n_trig == 0:
                    return None
                return dataclasses.replace(r, calls=n_trig)

            self.transport.log.rewrite_since(mark2, _reprice)
            # dynamic segments re-derive the tier split from the CORRECTED
            # records (merge at n_trig calls + the every-window probe)
            # instead of the trace-time cache snapshot
            tier_wire = {}
            for r in self.transport.log.since(mark2):
                if r.tag in ("merge", "probe"):
                    tier_wire[r.tier] = (tier_wire.get(r.tier, 0)
                                         + r.wire_bytes * r.calls)
        else:
            # each tier's measured per-window merge bytes is charged at that
            # link class's bandwidth (slow-DCN tier 1 vs ICI tier 0)
            tier_wire = self._merge_wire_by_tier(cache_key)
        wt = self.network.window_ticks(tau)
        for tier, total in tier_wire.items():
            wt += self.network.transfer_ticks(total / max(n_windows, 1),
                                              tier=tier)
        ticks = jnp.arange(1, n_windows + 1, dtype=jnp.int32) * wt
        if observe:
            self._emit_sync_obs(scheme=scheme, m=m, n_windows=n_windows,
                                tau=tau, wt=wt, tier_wire=tier_wire,
                                w_start=t0 // tau, curve=curve,
                                divergence=divergence, trig_np=trig_np)
            if quorum:
                self._emit_chaos_obs(w_start=t0 // tau, n_windows=n_windows,
                                     wt=wt, late_np=late_np)
        return SchemeResult(w_shared=w_final, wall_ticks=ticks,
                            distortion=curve), ms_out

    def _emit_chaos_obs(self, *, w_start: int, n_windows: int, wt: int,
                        late_np) -> None:
        """Render injected faults on the trace: one ``chaos_*`` span per
        scheduled event in this segment's window range (each on its own
        track — fault intervals overlap freely, and the trace checker pins
        same-track spans to nest-or-disjoint), plus counters for the
        quorum merge's late worker-windows and per-kind event totals."""
        tr, mt = self.tracer, self.metrics
        n_late = int(late_np.sum())
        if mt is not None and n_late:
            mt.counter("chaos_late_worker_windows").inc(n_late)
        if tr.enabled:
            tr.counter("chaos_late_workers_per_window", 0.0,
                       ts_us=float(w_start * wt))
            for wi in range(n_windows):
                tr.counter("chaos_late_workers_per_window",
                           float(late_np[:, wi].sum()),
                           ts_us=float((w_start + wi + 1) * wt))
        events_between = getattr(self.network, "events_between", None)
        if events_between is None:
            return
        for ev in events_between(w_start, w_start + n_windows):
            if mt is not None:
                mt.counter(f"chaos_{ev.kind}s").inc()
            if tr.enabled:
                dur = 1 if ev.kind == "kill" else ev.duration
                tr.add_span(
                    f"chaos_{ev.kind}", float(ev.window * wt),
                    float(dur * wt),
                    track=f"chaos {ev.kind} {ev.target}@{ev.window}",
                    window=ev.window, target=ev.target, kind=ev.kind)

    def _emit_sync_obs(self, *, scheme: str, m: int, n_windows: int,
                       tau: int, wt: int, tier_wire: dict, w_start: int,
                       curve, divergence, trig_np=None) -> None:
        """Mirror one sync segment onto the tick timeline and the registry.

        The window scan is a fused device program, so the per-worker
        timeline is *modeled* from the same ``NetworkModel`` arithmetic
        that produced ``wall_ticks`` (1 tick = 1 us in the trace): each
        worker computes for ``tau`` ticks, then the merge occupies the
        rest of the window, split across tiers in proportion to their
        measured wire bytes.  Distortion and divergence are the real
        per-window reduced values."""
        tr, mt = self.tracer, self.metrics
        curve_np = np.asarray(curve)
        div_np = None if divergence is None else np.asarray(divergence)
        n_trig = None if trig_np is None else int(trig_np.sum())
        if mt is not None:
            mt.counter("windows_total", scheme=scheme).inc(n_windows)
            if n_trig is not None:
                mt.counter("divergence_trigger", scheme=scheme).inc(n_trig)
                mt.counter("merge_skipped_total",
                           scheme=scheme).inc(n_windows - n_trig)
            h = mt.histogram("distortion", scheme=scheme)
            for c in curve_np:
                h.observe(float(c))
            if div_np is not None:
                g = mt.gauge("codebook_divergence", scheme=scheme)
                for dv in div_np:
                    g.set(float(dv))
            for tier, total in tier_wire.items():
                mt.counter(
                    "merge_wire_bytes",
                    tier="flat" if tier is None else tier,
                    scheme=scheme).inc(total)
        if not tr.enabled:
            return
        merge_total = max(wt - tau, 0)
        wire_sum = sum(tier_wire.values()) or 1
        # hoist the window-invariant geometry: track names and the tier
        # split are the same every window, only timestamps advance
        tracks = [f"worker {w}" for w in range(m)]
        tier_rows = []                   # (track, tier_attr, wire, dur)
        for tier, total in sorted(tier_wire.items(),
                                  key=lambda kv: (kv[0] is None,
                                                  kv[0] or 0)):
            tier_rows.append((
                "merge flat" if tier is None else f"merge tier {tier}",
                "flat" if tier is None else tier,
                int(round(total / max(n_windows, 1))),
                merge_total * (total / wire_sum)))
        add = tr.add_span
        for wi in range(n_windows):
            win = w_start + wi
            t_start = float(win * wt)
            for worker, track in enumerate(tracks):
                add("window", t_start, wt, track=track, window=win,
                    worker=worker, scheme=scheme)
                add("compute", t_start, tau, track=track, window=win,
                    worker=worker)
            t_m = t_start + tau
            # dynamic merges tag each span with whether this window's
            # divergence probe actually fired the sync
            tag = ({} if trig_np is None
                   else {"triggered": bool(trig_np[wi])})
            for track, tier_attr, wire, dur in tier_rows:
                add("merge", t_m, dur, track=track, tier=tier_attr,
                    wire_bytes=wire, window=win, scheme=scheme, **tag)
                t_m += dur
            t_end = t_start + wt
            tr.counter("distortion", float(curve_np[wi]), ts_us=t_end)
            if div_np is not None:
                tr.counter("codebook_divergence", float(div_np[wi]),
                           ts_us=t_end)
            if trig_np is not None:
                tr.counter("divergence_trigger", float(trig_np[wi]),
                           ts_us=t_end)

    # -- asynchronous scheme (eq. 9) ----------------------------------------

    def _run_async(self, mesh: Mesh, w0, data, eval_data, *, tau: int,
                   eps0: float, decay: float,
                   key: jax.Array | None) -> SchemeResult:
        axis = self._spec
        axes = self._axes
        m, n, _ = data.shape
        key = jax.random.PRNGKey(0) if key is None else key
        max_rounds = n // tau + 2
        lengths = self.network.round_lengths(key, m, max_rounds, tau)
        done_at = jnp.cumsum(lengths, axis=1)        # (M, max_rounds)
        eval_every = self.eval_every
        eval_ticks = np.arange(eval_every - 1, n, eval_every)
        transport = self.transport
        use_pallas = self.use_pallas
        vmem_budget = self.vmem_budget_bytes

        def body(w0_in, data_l, eval_l, done_at_l):
            stream = data_l[0]                       # (n, d)
            ev = eval_l[0]
            my_done_at = done_at_l[0]                # (max_rounds,)

            def tick(carry, z):
                w, w_srd, snap, dcur, dinf, nd, t, ridx, cs = carry
                eps = vq.default_steps(t + 1, eps0=eps0, decay=decay)
                # local VQ step (1st line of eq. 9), Pallas hot path
                if use_pallas:
                    counts, zsum = ops.vq_delta_routed(
                        z[None, :], w, budget_bytes=vmem_budget)
                    h = counts[:, None] * w - zsum
                else:
                    h = vq.H(z, w)
                step = eps * h
                w_tmp = w - step
                dcur = dcur + step

                done = nd == t                       # this worker completes?
                donef = done.astype(w.dtype)
                # masked merge: ONLY completing workers' in-flight deltas
                # land on the reducer (4th line of eq. 9)
                landed, cs = transport.masked_all_reduce(
                    dinf, donef, axis, state=cs, calls=n)
                w_srd = w_srd - landed
                # completed: adopt downloaded snapshot + replay local delta
                # (3rd line); others keep the plain step (2nd line)
                w = jnp.where(done, snap - dcur, w_tmp)
                snap = jnp.where(done, w_srd, snap)
                dinf = jnp.where(done, dcur, dinf)
                dcur = jnp.where(done, jnp.zeros_like(dcur), dcur)
                ridx = ridx + done.astype(jnp.int32)
                nd = jnp.where(
                    done,
                    jnp.take(my_done_at, jnp.minimum(ridx, max_rounds - 1)),
                    nd)
                return (w, w_srd, snap, dcur, dinf, nd, t + 1, ridx, cs), \
                    w_srd

            zeros = jnp.zeros_like(w0_in)
            cs0 = transport.init_state(w0_in)
            init = (w0_in, w0_in, w0_in, zeros, zeros, my_done_at[0],
                    jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
                    cs0)
            carry, traj = jax.lax.scan(tick, init, stream)
            w_srd_final = carry[1]
            with jax.named_scope(EVAL_PROBE_SCOPE):
                sel = traj[eval_ticks]               # (n_evals, kappa, d)
                c_local = jax.vmap(lambda w_: vq.distortion(ev, w_))(sel)
                curve, _ = transport.all_reduce(c_local, axis, op="mean",
                                                tag="eval")
            return w_srd_final, curve

        cache_key = ("async", mesh, w0.shape, data.shape, eval_data.shape,
                     tau, eps0, decay, eval_every, use_pallas, vmem_budget)

        def build():
            return jax.jit(jax.shard_map(
                body, mesh=mesh, in_specs=(P(), P(axis), P(axis), P(axis)),
                out_specs=(P(), P()),
                axis_names=frozenset(axes), check_vma=False))

        freshly_compiled = cache_key not in self._compiled
        w_final, curve = self._call_compiled(cache_key, build, w0, data,
                                             eval_data, done_at)
        if self.profiler is not None:
            # eq. 9 has no window barrier — attribute against the nominal
            # window count n // tau; the distortion probe runs once per
            # eval_every ticks, folded in as an effective per-window n_eval
            nominal_windows = max(n // tau, 1)
            self.profiler.note_segment(
                program=cache_key, scheme="async_delta",
                transport=self.transport.name, topology=self._topology_label,
                m=m, n_windows=nominal_windows, d=w0.shape[-1],
                kappa=w0.shape[0], tau=tau,
                n_eval=int(eval_data.shape[1] * len(eval_ticks)
                           / nominal_windows),
                compiled=freshly_compiled)
        if self.tracer.enabled or self.metrics is not None:
            self._emit_async_obs(m=m, n=n, tau=tau, done_at=done_at,
                                 eval_ticks=eval_ticks, curve=curve,
                                 cache_key=cache_key)
        return SchemeResult(
            w_shared=w_final,
            wall_ticks=jnp.asarray(eval_ticks + 1, jnp.int32),
            distortion=curve)

    def _emit_async_obs(self, *, m: int, n: int, tau: int, done_at,
                        eval_ticks, curve, cache_key: tuple) -> None:
        """Per-worker round timeline for eq. 9 (1 tick = 1 us in the trace).

        Each worker's round r computes for ``tau`` ticks and then keeps
        computing while its upload is in flight; the round *lands* at
        ``done_at[worker, r]``, where the in-flight delta joins the masked
        reduce.  Rendering compute and the in-flight ``merge`` span on the
        same worker track is what makes the paper's compute/communication
        overlap visible: worker A's merge span runs concurrently with
        worker B's compute span on the adjacent track.  Wire bytes are the
        per-tick masked-reduce charge attributed to the round's span."""
        tr, mt = self.tracer, self.metrics
        scheme = "async_delta"
        done_np = np.asarray(done_at)
        curve_np = np.asarray(curve)
        tier_wire = self._merge_wire_by_tier(cache_key)
        if mt is not None:
            h = mt.histogram("distortion", scheme=scheme)
            for c in curve_np:
                h.observe(float(c))
            rounds = int((done_np <= n).sum())
            mt.counter("async_rounds_total", scheme=scheme).inc(rounds)
            for tier, total in tier_wire.items():
                mt.counter(
                    "merge_wire_bytes",
                    tier="flat" if tier is None else tier,
                    scheme=scheme).inc(total)
        if not tr.enabled:
            return
        for worker in range(m):
            prev = 0
            for r in range(done_np.shape[1]):
                if prev >= n:
                    break
                end = min(int(done_np[worker, r]), n)
                if end <= prev:
                    continue
                track = f"worker {worker}"
                tr.add_span("round", prev, end - prev, track=track,
                            worker=worker, round=r, scheme=scheme)
                tr.add_span("compute", prev, min(tau, end - prev),
                            track=track, worker=worker, round=r)
                m_start = prev + min(tau, end - prev)
                for tier, total in tier_wire.items():
                    tr.add_span(
                        "merge", m_start, end - m_start,
                        track=track,
                        tier="flat" if tier is None else tier,
                        wire_bytes=int(round(total / n * (end - prev))),
                        worker=worker, round=r)
                prev = end
        for k, t in enumerate(eval_ticks):
            tr.counter("distortion", float(curve_np[k]), ts_us=float(t + 1))
