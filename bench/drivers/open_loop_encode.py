"""Traffic kind ``open_loop_encode``: encode requests at a fixed rate.

Set-up makes the stream from the seed, samples the codebook from it and
keeps ``pool`` further points as held-out queries.  It serves the
codebook through ``CodebookStore`` -> ``QuantizeService`` ->
``ShardedLookup`` with the service's defaults, and warms that path with
``warm_requests`` requests at the cell's rate.

The window is an open loop: request ``i`` is due at a time fixed in
advance and is submitted then, whether or not earlier ones are done, and
its latency counts from when it was due.  The gaps between requests are
the quantiles of an exponential distribution of mean ``1 / rate_rps``
(Poisson arrivals), and the request sizes are a fixed multiset with the
shares that ``request_rows`` gives, as ``[[rows, weight], ...]``; the
seed orders both, so every seed offers the same work.  A request that fails, or has no answer
``collect_timeout_s`` after the window's close, counts as beyond any
latency limit.

The check compares the codes and distances of every answered request of
the window with the plain reference.

``ctx.variant == "control"`` puts the reference's bfloat16 lookup in the
kernel's place, for reading the limits.
"""

from __future__ import annotations

import functools
import math
import threading
import time

import numpy as np


# A served code is wrong where its true distance lies above the nearest
# code's by more than this share of the two codes' scales together
# (|z|^2 + |w_code|^2 each).  Products at one bfloat16 pass, operands
# rounded to 8 bits, put a distance at most 2^-8 = 0.0039 of its scale
# off, so a sound lookup's order of two codes flips only under that.
FAR_GAP = 0.005


def schedule(n: int, rate: float, request_rows: list, seed: int
             ) -> tuple[np.ndarray, np.ndarray]:
    """(due times from the window's start in s, rows per request)."""
    import datagen

    u = (np.arange(n) + 0.5) / n
    gaps = datagen.permuted(-np.log1p(-u) / rate, seed, 2)
    k = np.array([r for r, _ in request_rows])
    p = np.array([w for _, w in request_rows], float)
    p /= p.sum()
    counts = np.floor(p * n).astype(int)
    extra = np.argsort(-(p * n - counts))[:n - counts.sum()]
    counts[extra] += 1
    sizes = datagen.permuted(np.repeat(k, counts), seed, 3)
    return np.cumsum(gaps), sizes


def nearest_rank(values: np.ndarray, q: float) -> float:
    """The ``q`` quantile of ``values`` by nearest rank (no interpolation,
    so an infinite latency stays infinite)."""
    s = np.sort(values)
    return float(s[max(0, math.ceil(q * len(s)) - 1)])


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.tr = ctx.traffic
        if ctx.variant not in (None, "control"):
            raise ValueError(f"unknown variant {ctx.variant!r}")
        self.attempted = 0
        self.failed = 0

    def setup(self) -> None:
        import jax

        import datagen
        from repro.serve import CodebookStore, QuantizeService, ShardedLookup

        cfg, tr, seed = self.cfg, self.tr, self.ctx.seed
        n, d = cfg["n_points"], cfg["d"]
        pts = datagen.mixture(datagen.key(seed, 0), n=n + tr["pool"], d=d,
                              n_centers=cfg["n_centers"], noise=cfg["noise"])
        self.w = datagen.sample_rows(datagen.key(seed, 1), pts[:n],
                                     cfg["kappa"])
        self.pool = np.asarray(pts[n:])
        del pts
        self.lookup = ShardedLookup(n_devices=1, mode="direct")
        if self.ctx.variant == "control":
            from reference import open_loop_encode as ref

            self.lookup.assign = ref.assign_bf16
        self.svc = QuantizeService(CodebookStore(self.w), self.lookup)
        self.svc.start()
        rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 4])
        warm_due, warm_rows = schedule(tr["warm_requests"], tr["rate_rps"],
                                       tr["request_rows"], seed)
        self._drive(warm_due, warm_rows, rng)
        self.rng = rng
        jax.block_until_ready(self.w)

    def _drive(self, due, rows, rng):
        """Submit requests of ``rows`` rows at ``due`` seconds from now;
        return (requests, answers, due times, answer times, submit lag).

        An answer is ``(codes, distances)``, or None where the request
        failed or had none ``collect_timeout_s`` after the last was due.
        The client keeps no future past its answer: thousands of live
        futures would be its own garbage for the process's collector to
        walk, and would show as the service's latency."""
        from jax.profiler import TraceAnnotation

        starts = rng.integers(0, len(self.pool) - rows.max(), len(rows))
        reqs = [self.pool[s:s + k] for s, k in zip(starts, rows)]
        n = len(reqs)
        answers = [None] * n
        done_at = np.full(n, np.nan)
        lag = np.empty(n)
        left = [n]
        lock, all_done = threading.Lock(), threading.Event()

        def on_done(fut, i):
            done_at[i] = time.monotonic()
            if fut.exception() is None:
                r = fut.result()
                answers[i] = (r.assign, r.mindist)
            with lock:
                left[0] -= 1
                if left[0] == 0:
                    all_done.set()

        submit = self.svc.submit
        t0 = time.monotonic() + 0.01
        for i, z in enumerate(reqs):
            target = t0 + due[i]
            now = time.monotonic()
            if target > now:
                with TraceAnnotation("sleep"):
                    time.sleep(target - now)
            with TraceAnnotation("submit"):
                lag[i] = time.monotonic() - target
                submit(z).add_done_callback(functools.partial(on_done, i=i))
        close = t0 + (due[-1] if n else 0.0)
        with TraceAnnotation("collect"):
            all_done.wait(max(close + self.tr["collect_timeout_s"]
                              - time.monotonic(), 0.0))
        return reqs, list(answers), t0 + due, done_at, lag

    def window(self, seconds: float, profile) -> tuple[dict, dict]:
        """The open loop for ``seconds``.  A traced run first drives
        ``trace_seconds`` of the same traffic under the profiler, stops
        it, and then runs the window untraced: the client's lag and the
        flush counters are the untraced window's."""
        tr = self.tr
        if profile is not None:
            due, rows = schedule(math.ceil(tr["rate_rps"] * tr["trace_seconds"]),
                                 tr["rate_rps"], tr["request_rows"], self.ctx.seed)
            profile.begin()
            self._drive(due, rows, self.rng)
            profile.end()
        stats = self.svc.stats
        rows0, flushes0 = stats.rows, stats.flushes
        full0 = stats.full_flushes
        due, rows = schedule(math.ceil(tr["rate_rps"] * seconds),
                             tr["rate_rps"], tr["request_rows"], self.ctx.seed)
        reqs, answers, due, done_at, lag = self._drive(due, rows, self.rng)
        self.svc.stop()
        lat_ms = np.where([a is None for a in answers], np.inf,
                          (done_at - due) * 1e3)
        self.reqs, self.answers, self.latency_ms = reqs, answers, lat_ms
        self.attempted = len(reqs)
        self.failed = int(sum(a is None for a in answers))
        flushes = stats.flushes - flushes0
        counters = {
            "requests": len(reqs), "failed": self.failed,
            "rows": int(sum(len(z) for z in reqs)),
            "flushes": flushes,
            "full_flushes": stats.full_flushes - full0,
            "rows_per_flush": ((stats.rows - rows0) / flushes
                               if flushes else None),
            "p99_ms": nearest_rank(lat_ms, 0.99),
            "lag_p99_ms": nearest_rank(lag, 0.99) * 1e3,
            "lag_max_ms": float(lag.max()) * 1e3,
        }
        return {"serve_p50_ms": nearest_rank(lat_ms, 0.50),
                "serve_p99_ms": counters["p99_ms"]}, counters

    def check(self) -> dict[str, float]:
        """Over every answered request of the window:

        - ``code_gap``: how far a served code's true distance lies above
          the nearest code's, over the row's scale |z|^2 + |w_code|^2
          (worst row), and ``code_gap_mean``, the same averaged over the
          rows;
        - ``far_rows``: rows whose served code is wrong beyond what the
          configuration's precision allows (``FAR_GAP``);
        - ``dist_err``: how far a served distance lies off the served
          code's true distance, over the same scale (worst row);
        - ``dist_err_mean``: the same, averaged over the rows;
        - ``unanswered``: requests of the window that failed, never
          came, or came with the wrong number of rows or codes outside
          the codebook."""
        from reference import open_loop_encode as ref

        kappa = self.cfg["kappa"]
        bad = 0
        for z, a in zip(self.reqs, self.answers):
            if (a is None or len(a[0]) != len(z) or len(a[1]) != len(z)
                    or a[0].min() < 0 or a[0].max() >= kappa):
                bad += 1
        ok = [i for i, a in enumerate(self.answers) if a is not None]
        if not ok:
            return {"code_gap": math.inf, "code_gap_mean": math.inf,
                    "far_rows": math.inf, "dist_err": math.inf,
                    "dist_err_mean": math.inf, "unanswered": float(bad)}
        z = np.concatenate([self.reqs[i] for i in ok])
        codes = np.concatenate([self.answers[i][0] for i in ok])
        mind = np.concatenate([self.answers[i][1] for i in ok])
        codes = np.clip(codes, 0, kappa - 1).astype(np.int32)
        code_gap, dist_err, both = ref.gaps(z, self.w, codes,
                                            mind.astype(np.float32))
        return {"code_gap": float(code_gap.max()),
                "code_gap_mean": float(code_gap.mean()),
                "far_rows": float(np.sum(both > FAR_GAP)),
                "dist_err": float(dist_err.max()),
                "dist_err_mean": float(dist_err.mean()),
                "unanswered": float(bad)}
