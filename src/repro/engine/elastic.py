"""``ElasticMeshExecutor`` — grow/shrink the worker set between merge windows.

The PR-1 ``MeshExecutor`` is static: M worker streams, M devices, one mesh
for the whole run.  A cloud deployment of the paper's schemes (CloudDALVQ:
up to 32 Azure VMs) sees workers *appear and disappear*; Patra's convergence
analysis of the displacement merge (arXiv:1012.5150) shows eq. (8) stays
sound under stale and late contributions, so a worker-set change can be a
**resharding event instead of a restart**:

    window k merge complete
        │
        ▼
    ResizeSchedule says M -> M' at window k
        │
        ├─ 1. checkpoint {w_srd, t, cursor} (Checkpointer, unsharded leaves)
        ├─ 2. late deltas: departing workers' in-flight windows merged via
        │     eq. (8) on the stale window, scaled by ``staleness_scale``
        ├─ 3. plan_remesh(survivors) -> build the M' worker mesh
        └─ 4. reshard the global sample pool into M' streams
        │
        ▼
    window k+1 runs on the new mesh (step schedule eps_t continues at t)

Wall-clock semantics: a window costs ``network.window_ticks(tau)`` ticks as
in the static executor; each resize event adds ``resize_cost_ticks`` (the
checkpoint + remesh + reshard pause, 0 by default — ``benchmarks/run.py
--suite elastic`` measures the real seconds).

Sample-budget semantics: the executor consumes one global pool of
``M0 * n`` points (the concatenation of the input streams, time-major), so
an elastic run and a fixed-M oracle given the same ``data`` see the same
total sample budget — the acceptance test pins their final distortion
within rtol 1e-2.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import comm
from repro.core import vq
from repro.core.schemes import SchemeResult
from repro.distributed import elastic as elastic_lib
from repro.engine import api
from repro.engine.mesh import MeshExecutor, make_worker_mesh
from repro.engine.network import InstantNetwork, NetworkModel
from repro.obs import NULL_TRACER, MetricsRegistry, Tracer
from repro.topology import Topology

ELASTIC_SCHEMES = ("average", "delta")


@dataclasses.dataclass(frozen=True)
class ResizeEvent:
    """At the end of global window ``window``, the worker set becomes
    ``new_m`` (clamped to the available devices by ``plan_remesh``)."""

    window: int
    new_m: int


class ResizeSchedule:
    """An ordered list of ``ResizeEvent``s, e.g. ``[(20, 4), (40, 8)]``."""

    def __init__(self, events):
        evs = [e if isinstance(e, ResizeEvent) else ResizeEvent(*e)
               for e in events]
        for e in evs:
            if e.window < 1:
                raise ValueError(
                    f"resize window must be >= 1 (after at least one merge), "
                    f"got {e.window}")
            if e.new_m < 1:
                raise ValueError(f"resize target M must be >= 1, "
                                 f"got {e.new_m}")
        windows = [e.window for e in evs]
        if sorted(windows) != windows or len(set(windows)) != len(windows):
            raise ValueError(
                f"resize windows must be strictly increasing, got {windows}")
        self.events: tuple[ResizeEvent, ...] = tuple(evs)

    @classmethod
    def parse(cls, spec: str) -> "ResizeSchedule":
        """Parse the CLI form ``"WINDOW:M,WINDOW:M,..."`` (e.g. "20:4,40:8")."""
        events = []
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            try:
                win, m = part.split(":")
                events.append(ResizeEvent(int(win), int(m)))
            except ValueError as e:
                raise ValueError(
                    f"bad resize spec {part!r} (want 'WINDOW:M'): {e}") from None
        if not events:
            raise ValueError(f"empty resize spec {spec!r}")
        return cls(events)

    def __iter__(self):
        return iter(self.events)

    def __len__(self):
        return len(self.events)


@dataclasses.dataclass
class ResizeStats:
    """What one resize event did (filled in by the executor at run time)."""

    window: int
    old_m: int
    new_m: int
    # from the shared remesh planner (distributed.elastic.plan_remesh).  The
    # VQ engine's worker mesh is 1-D (model axis = 1), so this is trivially
    # True today; it becomes informative once the elastic executor carries a
    # real TP axis (the LM side of plan_remesh already does).
    tp_preserved: bool
    late_points: int
    checkpoint_step: int | None
    wall_s: float
    # late_policy='merge' was requested but the remaining pool was too small
    # to give the departing workers their in-flight window — the event
    # degraded to 'drop' (the sample budget wins over the staleness model)
    late_skipped: bool = False
    # what fired this resize: 'schedule' (a planned ResizeEvent) or
    # 'chaos_kill' (an injected worker death treated as an unscheduled
    # shrink at the next window barrier)
    cause: str = "schedule"


class ElasticMeshExecutor:
    """``MeshExecutor`` with a ``ResizeSchedule``: the worker set grows and
    shrinks between merge windows without restarting the run.

    Parameters
    ----------
    schedule:         ``ResizeSchedule`` (or anything its ctor accepts).
    network:          ``NetworkModel`` for wall-tick accounting (instant
                      default, matching the paper's simulated architecture).
    checkpointer:     optional ``repro.checkpoint.Checkpointer``; when given,
                      every resize event first checkpoints
                      ``{w_srd, t, cursor, window, m}`` (blocking — the save
                      is part of the measured resize cost), and
                      ``resume=True`` restores the latest step and skips the
                      already-consumed prefix (the elastic restore path:
                      leaves are stored unsharded, so the new mesh size is
                      irrelevant to the read).
    late_policy:      'merge' (default) integrates departing workers'
                      in-flight window deltas with ``merge_late_delta`` —
                      eq. (8) on the stale window, damped by
                      ``staleness_scale(1, gamma)``; 'drop' discards them
                      (the restart-style baseline).
    resize_cost_ticks: wall ticks charged per resize event on the curve axis.
    topology:         optional ``repro.topology.Topology``.  A hierarchical
                      topology turns every resize into MULTI-HOST
                      elasticity: targets are clamped to whole host groups
                      (``workers_per_host`` stays fixed, the HOST tier
                      grows/shrinks), each segment runs on its own
                      ``(hosts, workers)`` mesh, and the shared transport
                      (typically ``HierarchicalTransport``) keeps per-tier
                      accounting across the whole run.
    """

    name = "elastic"

    def __init__(self, schedule, network: NetworkModel | None = None,
                 axis: str = "workers", *, use_pallas: bool = True,
                 transport: comm.Transport | str | None = None,
                 topology: Topology | None = None,
                 checkpointer=None, resume: bool = False,
                 late_policy: str = "merge", staleness_gamma: float = 0.5,
                 resize_cost_ticks: int = 0, on_window=None,
                 publish_every: int = 1, chaos=None,
                 checkpoint_every: int | None = None,
                 merge: str | None = None, quorum_frac: float = 0.6,
                 tracer: Tracer | None = None,
                 metrics: MetricsRegistry | None = None,
                 profiler=None):
        if not isinstance(schedule, ResizeSchedule):
            schedule = ResizeSchedule(schedule)
        if late_policy not in ("merge", "drop"):
            raise ValueError(
                f"late_policy must be 'merge' or 'drop', got {late_policy!r}")
        if resume and checkpointer is None:
            raise ValueError(
                "resume=True needs a checkpointer to restore from — "
                "silently restarting from scratch is not a resume")
        if publish_every < 1:
            raise ValueError(f"publish_every must be >= 1, "
                             f"got {publish_every}")
        if checkpoint_every is not None:
            if checkpoint_every < 1:
                raise ValueError(f"checkpoint_every must be >= 1, "
                                 f"got {checkpoint_every}")
            if checkpointer is None:
                raise ValueError(
                    "checkpoint_every needs a checkpointer to save to")
        if merge not in (None, "quorum"):
            raise ValueError(
                f"merge override must be None (scheme default) or 'quorum', "
                f"got {merge!r}")
        self.schedule = schedule
        self.network = network or InstantNetwork()
        self.topology = topology
        if topology is not None:
            axis = topology.worker_axis
        self.axis = axis
        self.use_pallas = use_pallas
        # ONE transport shared by every per-M segment executor, so the whole
        # elastic run streams into a single CommLog (segments + late deltas)
        self.transport = comm.get_transport(
            transport if transport is not None else "xla")
        self.last_comm: dict | None = None
        self.checkpointer = checkpointer
        self.resume = resume
        self.late_policy = late_policy
        self.staleness_gamma = staleness_gamma
        self.resize_cost_ticks = resize_cost_ticks
        # publication hook (see MeshExecutor.on_window): fires with the
        # GLOBAL window index — continuous across resize events — so a
        # CodebookStore sees one monotone stream over the whole elastic run
        self.on_window = on_window
        self.publish_every = publish_every
        # chaos schedule: its KILL events become unscheduled shrink-by-one
        # resizes at the next window barrier (the dead worker's in-flight
        # delta folds in via the late-delta path, exactly like a scheduled
        # departure); its slow/partition events ride the quorum merge's
        # late matrix through a ChaosNetwork passed as ``network``
        self.chaos = chaos
        # preemption-safe checkpointing: every ``checkpoint_every`` global
        # windows the publish hook saves the full elastic state, so a
        # killed process resumes mid-segment instead of from the last
        # resize event (serve-while-train restarts without failing queries)
        self.checkpoint_every = checkpoint_every
        self._last_ckpt_window = -1
        # merge override forwarded to every per-M segment executor
        self.merge = merge
        self.quorum_frac = quorum_frac
        # one tracer/registry shared by every per-M segment executor, so the
        # whole elastic run lands on one timeline (segments, resizes, comm)
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics
        if metrics is not None:
            self.transport.log.attach_metrics(metrics)
        # one profiler shared by every per-M segment executor: each segment
        # reports its own (m, n_windows) shapes via note_segment, and the
        # elastic run's total wall is attributed across them window-weighted
        self.profiler = profiler
        # one MeshExecutor per worker count — each holds its plan_remesh-built
        # mesh and its own compiled-program cache
        self._mesh_ex: dict[int, MeshExecutor] = {}
        self.resize_events: list[ResizeStats] = []

    # -- internals ----------------------------------------------------------

    @property
    def _hierarchical(self) -> bool:
        return self.topology is not None and not self.topology.is_flat

    def _executor_for(self, m: int, prev_m: int) -> MeshExecutor:
        """(Re)build the device mesh for ``m`` workers via ``plan_remesh``.

        On a hierarchical topology the worker count maps to WHOLE host
        groups (``workers_per_host`` fixed, the host tier resized), so the
        per-M executor carries its own ``hosts x workers_per_host``
        topology — a host-group departure/arrival is a resharding event on
        the host axis, not a restart."""
        if m not in self._mesh_ex:
            if self._hierarchical:
                wph = self.topology.workers_per_host
                topo = Topology.from_spec(
                    m, hosts=max(1, m // wph),
                    host_axis=self.topology.host_axis,
                    worker_axis=self.topology.worker_axis)
                self._mesh_ex[m] = MeshExecutor(
                    topology=topo, network=self.network,
                    transport=self.transport, use_pallas=self.use_pallas,
                    merge=self.merge, quorum_frac=self.quorum_frac,
                    staleness_gamma=self.staleness_gamma,
                    tracer=self.tracer, metrics=self.metrics,
                    profiler=self.profiler)
            else:
                plan = elastic_lib.plan_remesh(m, prev_data=prev_m,
                                               prev_model=1)
                mesh = make_worker_mesh(plan.data * plan.model, self.axis)
                self._mesh_ex[m] = MeshExecutor(
                    mesh=mesh, axis=self.axis, network=self.network,
                    transport=self.transport, use_pallas=self.use_pallas,
                    merge=self.merge, quorum_frac=self.quorum_frac,
                    staleness_gamma=self.staleness_gamma,
                    tracer=self.tracer, metrics=self.metrics,
                    profiler=self.profiler)
        return self._mesh_ex[m]

    def _segment_hook(self, window_idx: int, t0: int, cursor: int,
                      cur_m: int, tau: int, wt: int, tick_offset: int):
        """Build one segment's ``on_window`` adapter: forward the publish
        hook with the GLOBAL window index, and — when ``checkpoint_every``
        is set — save the full elastic state every N global windows, so a
        preempted process resumes mid-segment from the last periodic save
        instead of replaying everything since the last resize event."""
        periodic = (self.checkpointer is not None
                    and self.checkpoint_every is not None)
        if self.on_window is None and not periodic:
            return None

        def hook(wi, w, _off=window_idx, _t=t0, _cur=cursor, _m=cur_m,
                 _tick=tick_offset):
            gw = _off + wi
            if self.on_window is not None:
                self.on_window(gw, w)
            if (periodic and gw % self.checkpoint_every == 0
                    and gw > self._last_ckpt_window):
                with self.tracer.span("elastic.checkpoint", step=gw,
                                      periodic=True):
                    state = {"w_srd": jnp.asarray(jax.device_get(w)),
                             "t": np.asarray(_t + wi * tau, np.int64),
                             "cursor": np.asarray(_cur + wi * _m * tau,
                                                  np.int64),
                             "window": np.asarray(gw, np.int64),
                             "m": np.asarray(_m, np.int64),
                             "tick_offset": np.asarray(_tick + wi * wt,
                                                       np.int64)}
                    self.checkpointer.save(gw, state)
                self._last_ckpt_window = gw
                if self.metrics is not None:
                    self.metrics.counter("periodic_checkpoints").inc()

        return hook

    @staticmethod
    def _eval_streams(eval_pool: jax.Array, m: int) -> jax.Array:
        """Split the shared eval pool into m per-worker shards (the in-mesh
        curve pmean then evaluates (almost) the whole pool at every M)."""
        n_ev = eval_pool.shape[0] // m
        if n_ev == 0:
            raise ValueError(
                f"eval pool of {eval_pool.shape[0]} points cannot feed "
                f"M={m} workers")
        d = eval_pool.shape[-1]
        return eval_pool[: n_ev * m].reshape(m, n_ev, d)

    def _clamp_m(self, requested: int) -> tuple[int, "elastic_lib.RemeshPlan"]:
        n_dev = len(jax.devices())
        if self._hierarchical:
            # multi-host elasticity resizes WHOLE host groups: round the
            # target down to a multiple of workers_per_host (at least one
            # group), then clamp to the available devices
            wph = self.topology.workers_per_host
            m = max(wph, min(requested, n_dev) // wph * wph)
            if m > n_dev:
                raise ValueError(
                    f"one host group needs {wph} devices, have {n_dev} "
                    f"(hint: --xla_force_host_platform_device_count)")
            plan = elastic_lib.plan_remesh(m, prev_data=requested,
                                           prev_model=1)
            return m, plan
        plan = elastic_lib.plan_remesh(min(requested, n_dev),
                                       prev_data=requested, prev_model=1)
        return plan.data * plan.model, plan

    # -- public API ---------------------------------------------------------

    def run(self, scheme: str, w0: jax.Array, data: jax.Array,
            eval_data: jax.Array, *, tau: int, eps0: float = 0.5,
            decay: float = 1.0, key: jax.Array | None = None) -> SchemeResult:
        del key  # sync schemes are deterministic; kept for Executor protocol
        t_wall = time.perf_counter()
        with self.tracer.span("elastic.run", scheme=scheme, executor=self.name,
                              m=data.shape[0] if data.ndim == 3 else None):
            res = self._run(scheme, w0, data, eval_data, tau=tau, eps0=eps0,
                            decay=decay)
        wall_s = time.perf_counter() - t_wall
        if self.metrics is not None:
            self.metrics.histogram("run_wall_s", executor=self.name,
                                   scheme=scheme).observe(wall_s)
        if self.profiler is not None:
            # segments were noted by the per-M executors' _run_sync calls;
            # attribute the whole elastic run's wall across them
            self.profiler.finish_run(wall_s)
        return res

    def _run(self, scheme: str, w0: jax.Array, data: jax.Array,
             eval_data: jax.Array, *, tau: int, eps0: float,
             decay: float) -> SchemeResult:
        api.validate_scheme(scheme)
        if scheme not in ELASTIC_SCHEMES:
            raise ValueError(
                f"elastic execution supports {ELASTIC_SCHEMES}; "
                f"async_delta has no window barrier to resize at")
        if data.ndim != 3:
            raise ValueError(f"data must be (M, n, d), got {data.shape}")
        if eval_data.ndim != 3:
            raise ValueError(
                f"eval_data must be (M, n_eval, d), got {eval_data.shape}")
        m0, n, d = data.shape
        if n < tau:
            raise ValueError(
                f"need at least one tau={tau} window per worker, got n={n}")

        # one global pool, time-major: elastic and fixed-M runs on the same
        # `data` consume the same total sample budget
        pool = data.transpose(1, 0, 2).reshape(-1, d)
        eval_pool = eval_data.reshape(-1, d)
        total = pool.shape[0]
        wt = self.network.window_ticks(tau)

        cur_m, _ = self._clamp_m(m0)
        w_srd, t0, cursor, window_idx, tick_offset = w0, 0, 0, 0, 0
        self.resize_events = []
        comm_mark = self.transport.log.mark()

        resumed = False
        if self.resume:
            latest = self.checkpointer.latest_step()
            if latest is None:
                raise ValueError(
                    f"resume=True but no checkpoint found in "
                    f"{self.checkpointer.dir!r} — silently restarting from "
                    f"scratch is not a resume (drop resume for a fresh run)")
            st = self.checkpointer.restore(latest, self._state_target(w0))
            w_srd = st["w_srd"]
            t0 = int(st["t"])
            cursor = int(st["cursor"])
            window_idx = int(st["window"])
            cur_m, _ = self._clamp_m(int(st["m"]))
            tick_offset = int(st["tick_offset"])
            resumed = True

        # one merged boundary list: scheduled resizes plus injected worker
        # deaths, each an (window, cause, payload) barrier the segment loop
        # stops at.  A chaos kill's target M is resolved at fire time
        # (shrink the CURRENT worker set by one) — two kills at different
        # windows compose to M-2 without the schedule knowing M up front.
        boundaries: list[tuple[int, str, int]] = [
            (e.window, "schedule", e.new_m)
            for e in self.schedule if e.window > window_idx]
        if self.chaos is not None:
            boundaries += [
                (ce.window, "chaos_kill", -1)
                for ce in self.chaos.kill_events if ce.window > window_idx]
        boundaries.sort(key=lambda b: (b[0], b[1] != "schedule"))
        ei = 0
        curves: list[np.ndarray] = []
        ticks: list[np.ndarray] = []
        prev_m = cur_m
        self._last_ckpt_window = window_idx

        while True:
            target = boundaries[ei][0] if ei < len(boundaries) else None
            max_w = (total - cursor) // (cur_m * tau)
            want_w = max_w if target is None else (target - window_idx)
            seg_w = min(max_w, want_w)
            if seg_w > 0:
                seg_pts = cur_m * seg_w * tau
                with self.tracer.span("elastic.resplit", m=cur_m,
                                      windows=seg_w, points=seg_pts):
                    # reshard the global pool into cur_m time-major streams
                    seg = pool[cursor: cursor + seg_pts]
                    seg_data = seg.reshape(
                        seg_w * tau, cur_m, d).transpose(1, 0, 2)
                    seg_eval = self._eval_streams(eval_pool, cur_m)
                mex = self._executor_for(cur_m, prev_m)
                # assign unconditionally: the per-M executors are cached, so
                # a previous run's publish adapter must not survive into a
                # run with the hook cleared
                mex.on_window = self._segment_hook(
                    window_idx, t0, cursor, cur_m, tau, wt, tick_offset)
                mex.publish_every = self.publish_every
                res = mex.run_segment(
                    scheme, w_srd, seg_data, seg_eval, tau=tau, eps0=eps0,
                    decay=decay, t0=t0)
                w_srd = res.w_shared
                curves.append(np.asarray(res.distortion))
                ticks.append(tick_offset + np.asarray(res.wall_ticks))
                tick_offset += seg_w * wt
                cursor += seg_pts
                t0 += seg_w * tau
                window_idx += seg_w
            if target is None or window_idx < target:
                break  # no more events, or the pool ran dry before the next
            win, cause, payload = boundaries[ei]
            ei += 1
            prev_m = cur_m
            # an injected death shrinks the CURRENT worker set by one; the
            # dead worker's in-flight window folds in via the late-delta
            # path exactly like a scheduled departure
            new_m_req = payload if cause == "schedule" else max(1, cur_m - 1)
            w_srd, cur_m, cursor = self._do_resize(
                ResizeEvent(win, new_m_req), w_srd, cur_m, pool, cursor, t0,
                window_idx, tick_offset, tau=tau, eps0=eps0, decay=decay,
                cause=cause)
            tick_offset += self.resize_cost_ticks

        self.last_comm = comm.CommLog.summarize(
            self.transport.log.since(comm_mark))
        if not curves:
            if resumed:
                # the checkpoint captured an already-complete run: nothing
                # left to execute — report the restored state as the result
                c = vq.distortion(eval_pool, w_srd)
                return SchemeResult(
                    w_shared=w_srd,
                    wall_ticks=jnp.asarray([tick_offset], jnp.int32),
                    distortion=jnp.asarray([c]))
            raise ValueError(
                "elastic run produced no windows — pool exhausted before the "
                "first merge (reduce tau or provide more data)")
        return SchemeResult(
            w_shared=w_srd,
            wall_ticks=jnp.asarray(np.concatenate(ticks), jnp.int32),
            distortion=jnp.asarray(np.concatenate(curves)))

    # -- resize event -------------------------------------------------------

    @staticmethod
    def _state_target(w0: jax.Array) -> dict:
        return {"w_srd": jnp.zeros_like(w0),
                "t": np.zeros((), np.int64),
                "cursor": np.zeros((), np.int64),
                "window": np.zeros((), np.int64),
                "m": np.zeros((), np.int64),
                "tick_offset": np.zeros((), np.int64)}

    def _do_resize(self, ev: ResizeEvent, w_srd, cur_m: int, pool, cursor: int,
                   t0: int, window_idx: int, tick_offset: int, *, tau: int,
                   eps0: float, decay: float, cause: str = "schedule"):
        t_start = time.perf_counter()
        ckpt_step = None
        new_m, plan = self._clamp_m(ev.new_m)
        if cause == "chaos_kill" and self.metrics is not None:
            self.metrics.counter("chaos_kills").inc()
        with self.tracer.span("elastic.resize", window=window_idx, old_m=cur_m,
                              new_m=new_m, cause=cause):
            # un-commit the shared prototypes from the old mesh: the segment
            # output is sharded over the outgoing device set, and the next
            # shard_map runs on a different one
            w_srd = jnp.asarray(jax.device_get(w_srd))
            late_pts = 0
            late_skipped = False
            if new_m < cur_m and self.late_policy == "merge":
                # the departed workers were mid-flight on their next window
                # when the resize fired: their deltas arrive late, computed
                # against the stale shared version, and are summed in via
                # eq. (8) damped by one window of staleness
                n_dep = cur_m - new_m
                need = n_dep * tau
                if pool.shape[0] - cursor >= need:
                    with self.tracer.span("elastic.late_delta", n_dep=n_dep,
                                          points=need):
                        d = pool.shape[-1]
                        late = pool[cursor: cursor + need].reshape(
                            n_dep, tau, d)
                        cursor += need
                        late_pts = need
                        deltas, _ = jax.vmap(
                            lambda z: vq.window_displacement(
                                w_srd, z, jnp.asarray(t0, jnp.int32),
                                eps0=eps0, decay=decay))(late)
                        w_srd = elastic_lib.merge_late_delta(
                            w_srd, jnp.sum(deltas, axis=0), delay_windows=1,
                            gamma=self.staleness_gamma)
                        # the departing workers' deltas ride the same
                        # accounting stream as the collectives: each uploads
                        # one (kappa, d) f32 displacement to the survivors,
                        # host-side.  On a hierarchical topology the departed
                        # workers were whole host groups, so the upload
                        # crossed the inter-host tier.
                        self.transport.record_host_transfer(
                            logical_bytes=4 * int(w_srd.size),
                            wire_bytes=4 * int(w_srd.size),
                            participants=n_dep, axis=self.axis,
                            tag="late_delta",
                            tier=1 if self._hierarchical else None)
                    if self.metrics is not None:
                        # every departing worker's delta lands exactly one
                        # window stale (delay_windows=1 above)
                        self.metrics.counter("staleness_windows").inc(n_dep)
                        self.metrics.counter("late_delta_points").inc(need)
                else:
                    late_skipped = True  # pool too dry; recorded, not silent
                    if self.metrics is not None:
                        self.metrics.counter("late_delta_skipped").inc()
            # rebuild the mesh for the survivors (cached per M)
            with self.tracer.span("elastic.remesh", m=new_m):
                self._executor_for(new_m, cur_m)
                jax.block_until_ready(w_srd)
            if self.checkpointer is not None:
                # post-event state: a resume from here continues
                # bit-identically (late deltas already integrated, cursor
                # already advanced)
                with self.tracer.span("elastic.checkpoint", step=window_idx):
                    state = {"w_srd": w_srd,
                             "t": np.asarray(t0, np.int64),
                             "cursor": np.asarray(cursor, np.int64),
                             "window": np.asarray(window_idx, np.int64),
                             "m": np.asarray(new_m, np.int64),
                             "tick_offset": np.asarray(
                                 tick_offset + self.resize_cost_ticks,
                                 np.int64)}
                    self.checkpointer.save(window_idx, state)
                    ckpt_step = window_idx
        wall_s = time.perf_counter() - t_start
        if self.metrics is not None:
            self.metrics.counter("resize_events").inc()
            self.metrics.histogram("resize_wall_s").observe(wall_s)
        self.resize_events.append(ResizeStats(
            window=window_idx, old_m=cur_m, new_m=new_m,
            tp_preserved=plan.tp_preserved, late_points=late_pts,
            checkpoint_step=ckpt_step,
            wall_s=wall_s,
            late_skipped=late_skipped,
            cause=cause))
        return w_srd, new_m, cursor
