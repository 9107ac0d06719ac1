"""Traffic kind ``train_stream``: the paper's training job on a stream.

Set-up makes the stream, the held-out set and the starting codebook on
the device from the seed, slices the stream into chunks of one fixed
shape, one share per worker, and builds one ``MeshExecutor``.  It then
drives that executor through the first ``check_chunks`` chunks with the
very call the window makes (``run_segment``), which compiles (or loads)
the one program the window runs.  The window keeps calling it on the next
chunk, the step schedule continuing from chunk to chunk as in one long
run, with at most ``in_flight`` chunks queued on the device, until
``seconds`` have passed and the last chunk has finished.  A traced run
first traces ``trace_chunks`` chunks, then runs the same window.

The check follows the first chunks with the plain reference and compares
the distortion the engine reports after each, and the norm of the
codebook's change after the first and after the last of them.

``ctx.variant`` puts the reference in the program's place for the first
chunks, for reading the limits: ``"control"`` in bfloat16, ``"half"`` and
``"no_merge"`` with those faults planted (see ``reference/train_stream``).
"""

from __future__ import annotations

import collections
import time

import numpy as np

VARIANTS = (None, "control", "half", "no_merge")


def rel_gap(got: float, want: float) -> float:
    return abs(got - want) / abs(want)


def _spread(secs) -> list[float] | None:
    """[least, median, most] of ``secs``, in ms."""
    if not len(secs):
        return None
    return [float(np.min(secs)) * 1e3, float(np.median(secs)) * 1e3,
            float(np.max(secs)) * 1e3]


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        tr = ctx.traffic
        self.m = tr["workers"]
        if len(ctx.devices) != self.m:
            raise ValueError(f"{self.m} workers need {self.m} chips, the "
                             f"cell has {len(ctx.devices)}")
        if ctx.variant not in VARIANTS:
            raise ValueError(f"unknown variant {ctx.variant!r}")
        self.chunk = tr["chunk_points"]
        self.n_check = tr["check_chunks"]
        self.in_flight = tr["in_flight"]
        self.trace_chunks = tr["trace_chunks"]
        self.attempted = 0
        self.failed = 0

    # -- set-up ---------------------------------------------------------

    def setup(self) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        import datagen
        from repro.engine import MeshExecutor
        from repro.topology import make_worker_mesh

        cfg, m = self.cfg, self.m
        n, d, n_eval = cfg["n_points"], cfg["d"], cfg["n_eval"]
        per = n // m
        if per % self.chunk or n_eval % m or n_eval % 512:
            raise ValueError("chunk_points must divide each worker's share, "
                             "and workers and 512 must divide n_eval")
        pts = datagen.mixture(datagen.key(self.ctx.seed, 0), n=n + n_eval,
                              d=d, n_centers=cfg["n_centers"],
                              noise=cfg["noise"])
        w0 = datagen.sample_rows(datagen.key(self.ctx.seed, 1), pts[:n],
                                 cfg["kappa"])
        mesh = make_worker_mesh(m, "workers")
        shard = NamedSharding(mesh, P("workers"))
        stream = pts[:n].reshape(m, per, d)
        self.chunks = [jax.device_put(stream[:, i:i + self.chunk], shard)
                       for i in range(0, per, self.chunk)]
        self.ev = jax.device_put(pts[n:].reshape(m, n_eval // m, d), shard)
        self.w0 = jax.device_put(w0, NamedSharding(mesh, P()))
        del pts, stream
        self.ex = MeshExecutor(mesh=mesh, transport=cfg["transport"])
        if self.ctx.variant is None:
            self._first_steps()
        else:
            self._reference_first_steps()

    def _step(self, w, k: int, t: int):
        cfg = self.cfg
        return self.ex.run_segment(
            cfg["scheme"], w, self.chunks[k % len(self.chunks)], self.ev,
            tau=cfg["tau"], eps0=cfg["eps0"], decay=cfg["decay"], t0=t)

    def _first_steps(self) -> None:
        import jax

        w, t = self.w0, 0
        self.first_w, self.first_loss = [], []
        for k in range(self.n_check):
            res = self._step(w, k, t)
            w, t = res.w_shared, t + self.chunk
            self.first_w.append(w)
            self.first_loss.append(res.distortion[-1])
        jax.block_until_ready(w)
        self.w, self.t, self.k = w, t, self.n_check

    def _reference_first_steps(self) -> None:
        """The reference, as the variant says, in the program's place."""
        import jax
        import jax.numpy as jnp

        from reference import train_stream as ref

        variant = self.ctx.variant
        ws, losses = ref.follow(
            self._one_chip(self.w0),
            [self._one_chip(c) for c in self.chunks[:self.n_check]],
            self._one_chip(self.ev), tau=self.cfg["tau"],
            eps0=self.cfg["eps0"], decay=self.cfg["decay"],
            dtype=jnp.bfloat16 if variant == "control" else jnp.float32,
            fault=None if variant == "control" else variant)
        self.first_w, self.first_loss = ws, losses
        jax.block_until_ready(ws[-1])
        self.w, self.t, self.k = self.w0, 0, 0

    def _one_chip(self, x):
        import jax

        return jax.device_put(x, self.ctx.devices[0])

    # -- the measured window -------------------------------------------

    def _dispatch(self):
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("chunk"):
            res = self._step(self.w, self.k, self.t)
        self.w = res.w_shared
        self.k += 1
        self.t += self.chunk
        self.attempted += 1
        return self.w

    def window(self, seconds: float, profile) -> tuple[dict, dict]:
        import jax
        from jax.profiler import TraceAnnotation

        counters = {"traced_points_per_worker": 0}
        if profile is not None:
            profile.begin()
            for _ in range(self.trace_chunks):
                self._dispatch()
            with TraceAnnotation("collect"):
                jax.block_until_ready(self.w)
            profile.end()
            counters["traced_points_per_worker"] = (self.trace_chunks
                                                    * self.chunk)
        queue = collections.deque()
        dispatch_s, finished = [], []
        t_start = time.perf_counter()
        while time.perf_counter() - t_start < seconds:
            t = time.perf_counter()
            queue.append(self._dispatch())
            dispatch_s.append(time.perf_counter() - t)
            while len(queue) > self.in_flight:
                with TraceAnnotation("collect"):
                    jax.block_until_ready(queue.popleft())
                finished.append(time.perf_counter())
        while queue:
            jax.block_until_ready(queue.popleft())
            finished.append(time.perf_counter())
        wall = finished[-1] - t_start
        done = len(finished)
        points = done * self.chunk * self.m
        # host time to enqueue a chunk, and the time between two chunks'
        # ends once the queue is full: a slow run shows whether the host
        # or the device held it back, and whether all along or once
        gaps = np.diff(finished[self.in_flight:])
        counters.update(chunks=done, points=points, window_s=wall,
                        dispatch_ms=_spread(dispatch_s),
                        chunk_gap_ms=_spread(gaps))
        return {"train_points_per_s": points / wall}, counters

    # -- the check -------------------------------------------------------

    def check(self) -> dict[str, float]:
        """Gaps between the program and the reference over the first
        chunks, each relative to the reference's value:

        - ``loss_gap``: the held-out distortion after each chunk (worst);
        - ``step1_gap``: the norm of the codebook's change after chunk 1;
        - ``change_gap``: the norm of its change after the last one."""
        import jax.numpy as jnp

        from reference import train_stream as ref

        cfg = self.cfg
        prog_w = [np.asarray(w, np.float64) for w in self.first_w]
        prog_loss = [float(x) for x in self.first_loss]
        self.first_w = self.first_loss = None
        self.chunks = self.chunks[:self.n_check]
        self.ex = self.w = None
        w0 = self._one_chip(self.w0)
        ref_w, ref_loss = ref.follow(
            w0, [self._one_chip(c) for c in self.chunks],
            self._one_chip(self.ev), tau=cfg["tau"], eps0=cfg["eps0"],
            decay=cfg["decay"], dtype=jnp.float32)
        w0 = np.asarray(w0, np.float64)
        ref_w = [np.asarray(w, np.float64) for w in ref_w]

        def change(w):
            return float(np.linalg.norm(w - w0))

        return {
            "loss_gap": max(rel_gap(p, r)
                            for p, r in zip(prog_loss, ref_loss)),
            "step1_gap": rel_gap(change(prog_w[0]), change(ref_w[0])),
            "change_gap": rel_gap(change(prog_w[-1]), change(ref_w[-1])),
        }
