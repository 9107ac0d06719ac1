"""Benchmark harness — one function per paper table/figure + kernel/system
benchmarks.  Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
    PYTHONPATH=src python -m benchmarks.run --suite engine   # executor bench
    PYTHONPATH=src python -m benchmarks.run --suite elastic  # resize cost
    PYTHONPATH=src python -m benchmarks.run --suite serve    # lookup service
    PYTHONPATH=src python -m benchmarks.run --suite hier     # flat vs 2-tier
    PYTHONPATH=src python -m benchmarks.run --suite obs      # tracing cost
    PYTHONPATH=src python -m benchmarks.run --suite chaos    # fault injection
"""

from __future__ import annotations

import argparse
import json
import os
import time

from repro.xla_flags import force_host_devices

# the engine suite runs MeshExecutor up to M=8 workers; harmless for the
# single-device benches
force_host_devices(8)

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache


def _time_call(fn, *args, iters=5, warmup=2) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6  # us


def bench_fig1() -> list[str]:
    from benchmarks.paper_figs import fig1_averaging
    t0 = time.perf_counter()
    res = fig1_averaging()
    us = (time.perf_counter() - t0) * 1e6
    final = {m: float(c[-1]) for m, c in res["curves"].items()}
    ratio = final[10] / final[1]
    return [f"fig1_averaging,{us:.0f},final_C(M=10)/C(M=1)={ratio:.3f}"
            f" (paper: ~1 — no speed-up)"]


def bench_fig2() -> list[str]:
    from benchmarks.paper_figs import fig2_delta
    t0 = time.perf_counter()
    res = fig2_delta()
    us = (time.perf_counter() - t0) * 1e6
    final = {m: float(c[-1]) for m, c in res["curves"].items()}
    ratio = final[10] / final[1]
    return [f"fig2_delta,{us:.0f},final_C(M=10)/C(M=1)={ratio:.3f}"
            f" (paper: <1 — speed-up)"]


def bench_fig3() -> list[str]:
    from benchmarks.paper_figs import fig3_async
    t0 = time.perf_counter()
    res = fig3_async()
    us = (time.perf_counter() - t0) * 1e6
    final = {m: float(c[-1]) for m, c in res["curves"].items()}
    ratio = final[10] / final[1]
    return [f"fig3_async,{us:.0f},final_C(M=10)/C(M=1)={ratio:.3f}"
            f" (paper: async ~ sync delta)"]


def bench_fig4() -> list[str]:
    from benchmarks.paper_figs import fig4_scaleup
    t0 = time.perf_counter()
    res = fig4_scaleup()
    us = (time.perf_counter() - t0) * 1e6
    t = res["ticks_to_threshold"]
    base = t.get(1, -1)
    speed32 = (base / t[32]) if t.get(32, -1) > 0 and base > 0 else float("nan")
    return [f"fig4_scaleup,{us:.0f},speedup(M=32)={speed32:.1f}x ticks={t}"]


def bench_vq_kernel() -> list[str]:
    """Pallas kernel vs jnp reference (interpret mode on CPU: correctness
    harness; wall time is NOT TPU-indicative — roofline numbers live in
    EXPERIMENTS.md §Roofline)."""
    from repro.kernels import ops, ref
    rows = []
    key = jax.random.PRNGKey(0)
    for (b, k, d) in [(4096, 256, 64), (16384, 1024, 64)]:
        z = jax.random.normal(key, (b, d))
        w = jax.random.normal(jax.random.fold_in(key, 1), (k, d))
        us_ref = _time_call(lambda: ref.vq_delta_ref(z, w))
        c_ref, s_ref = ref.vq_delta_ref(z, w)
        c, s = ops.vq_delta(z, w)
        err = float(jnp.max(jnp.abs(s - s_ref)))
        # analytic TPU roofline for the fused kernel (bf16):
        flops = 2 * b * k * d + 2 * b * k * d  # dist matmul + scatter matmul
        bytes_ = (b * d + k * d * 2 + k) * 4
        t_c = flops / 197e12
        t_m = bytes_ / 819e9
        bound = "compute" if t_c > t_m else "memory"
        rows.append(
            f"vq_delta_b{b}_k{k}_d{d},{us_ref:.0f},"
            f"oracle_maxerr={err:.1e} tpu_bound={bound}"
            f" t_c={t_c * 1e6:.1f}us t_m={t_m * 1e6:.1f}us")
    return rows


def bench_merge_strategies() -> list[str]:
    """Paper schemes as LM training merge strategies: pod-axis collective
    bytes per step from the multi-pod dry-run records (populate with
    ``python -m repro.launch.dryrun --arch granite_8b --shape train_4k
    --multi-pod --merge <m>``)."""
    import json
    import os
    rows = []
    path = "benchmarks/results/dryrun.json"
    if not os.path.exists(path):
        return ["merge_strategies,0,missing benchmarks/results/dryrun.json"]
    with open(path) as f:
        data = json.load(f)
    recs = [r for r in data
            if r.get("mesh") == "2x16x16" and r.get("status") == "ok"
            and r.get("merge", "none") != "none"]
    if not recs:
        return ["merge_strategies,0,no multi-pod merge records yet"]
    for rec in recs:
        div = rec.get("per_step_divisor", 1)
        per_step = rec["collectives"]["total_bytes"] / div
        rows.append(
            f"merge_{rec['arch']}_{rec['merge']},"
            f"{rec['compile_s'] * 1e6:.0f},"
            f"coll_bytes_per_step={per_step:.3e}")
    return rows


def bench_training_throughput() -> list[str]:
    """Wall-clock CPU throughput of the end-to-end train step (tiny model) —
    exercises the full substrate (data, model, optimizer)."""
    from repro.configs import registry
    from repro.data.pipeline import DataConfig, lm_batch
    from repro.optim import optimizers
    from repro.training import steps as steps_lib
    cfg = registry.get_smoke_config("granite_8b")
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=8)
    opt = optimizers.adamw(1e-3)
    step = jax.jit(steps_lib.make_train_step(cfg, opt))
    state = steps_lib.init_train_state(cfg, opt, jax.random.PRNGKey(0))
    batch = lm_batch(dcfg, 0)
    state, _ = step(state, batch)  # compile
    us = _time_call(lambda: step(state, batch)[0]["step"])
    toks = dcfg.seq_len * dcfg.global_batch
    return [f"train_step_smoke,{us:.0f},tokens_per_s={toks / us * 1e6:.0f}"]


def bench_decode_throughput() -> list[str]:
    from repro.configs import registry
    from repro.training import steps as steps_lib
    from repro.models.api import get_api
    cfg = registry.get_smoke_config("granite_8b")
    api = get_api(cfg)
    params = api.init(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((8, 16), jnp.int32)}
    cache = api.init_cache(params, batch, 128)
    step = jax.jit(steps_lib.make_serve_step(cfg))
    tok = jnp.zeros((8, 1), jnp.int32)
    _, cache2 = step(params, cache, tok)  # compile
    us = _time_call(lambda: step(params, cache, tok)[0])
    return [f"decode_step_smoke,{us:.0f},tokens_per_s={8 / us * 1e6:.0f}"]


def bench_engine(*, quick: bool = False,
                 out_path: str = "BENCH_engine.json") -> list[str]:
    """SimExecutor vs MeshExecutor wall-clock per processed point, M = 1..8.

    Each executor runs the delta scheme end to end (compile excluded via a
    warm-up run); "per point" divides by the M*n points the run consumes, so
    the number is the engine's cost of one unit of the paper's work.  Writes
    the full trajectory record to ``BENCH_engine.json``."""
    from repro.data import synthetic
    from repro.engine import InstantNetwork, get_executor

    n, d, kappa, tau = (400 if quick else 1000), 8, 16, 10
    key = jax.random.PRNGKey(0)
    kd, kw = jax.random.split(key)
    rows, records = [], []
    for m in (1, 2, 4, 8):
        data = synthetic.replicate_stream(kd, m, n=n, d=d)
        eval_data = data[:, :200]
        w0 = synthetic.kmeanspp_init(kw, data.reshape(-1, d), kappa)
        for name in ("sim", "mesh"):
            ex = get_executor(name, network=InstantNetwork())
            run = lambda: jax.block_until_ready(  # noqa: E731
                ex.run("delta", w0, data, eval_data, tau=tau).w_shared)
            run()  # compile
            samples = []
            for _ in range(3):  # best-of-3: single runs are too noisy to gate
                t0 = time.perf_counter()
                res = ex.run("delta", w0, data, eval_data, tau=tau)
                jax.block_until_ready(res.w_shared)
                samples.append(time.perf_counter() - t0)
            wall_s = min(samples)
            points = m * (n // tau) * tau
            us_per_point = wall_s / points * 1e6
            rows.append(f"engine_{name}_M{m},{wall_s * 1e6:.0f},"
                        f"us_per_point={us_per_point:.3f}"
                        f" final_C={float(res.distortion[-1]):.5f}")
            records.append({
                "executor": name, "scheme": "delta", "m": m, "n": n,
                "d": d, "kappa": kappa, "tau": tau,
                "wall_s": wall_s, "us_per_point": us_per_point,
                "wall_samples": samples,
                "wall_ticks": np.asarray(res.wall_ticks).tolist(),
                "distortion": np.asarray(res.distortion,
                                         np.float64).tolist(),
            })

    with open(out_path, "w") as f:
        json.dump({"suite": "engine", "devices": len(jax.devices()),
                   "backend": jax.default_backend(),
                   "results": records}, f, indent=1)
    rows.append(f"engine_trajectories,0,wrote {out_path} "
                f"({len(records)} records)")
    return rows


def bench_elastic(*, quick: bool = False,
                  out_path: str = "BENCH_elastic.json") -> list[str]:
    """What does a resize event cost?  An 8->4->8 elastic run vs the fixed-M
    mesh run on the same sample budget: per-event pause (checkpoint + remesh
    + reshard, measured seconds), amortized per-window overhead, and the
    final-distortion gap.  Writes the full record to ``BENCH_elastic.json``."""
    import tempfile

    from repro.checkpoint.checkpointing import Checkpointer
    from repro.data import synthetic
    from repro.engine import (ElasticMeshExecutor, InstantNetwork,
                              MeshExecutor, ResizeSchedule)

    m0, n, d, kappa, tau = 8, (400 if quick else 1000), 8, 16, 10
    m0 = min(m0, len(jax.devices()))
    key = jax.random.PRNGKey(0)
    kd, kw = jax.random.split(key)
    data = synthetic.replicate_stream(kd, m0, n=n, d=d)
    eval_data = data[:, :200]
    w0 = synthetic.kmeanspp_init(kw, data.reshape(-1, d), kappa)
    n_windows = n // tau
    schedule = ResizeSchedule([(n_windows // 2, max(1, m0 // 2)),
                               (n_windows, m0)])

    fixed = MeshExecutor(network=InstantNetwork())
    run_fixed = lambda: jax.block_until_ready(  # noqa: E731
        fixed.run("delta", w0, data, eval_data, tau=tau).w_shared)
    run_fixed()  # compile
    t0 = time.perf_counter()
    res_fixed = fixed.run("delta", w0, data, eval_data, tau=tau)
    jax.block_until_ready(res_fixed.w_shared)
    wall_fixed = time.perf_counter() - t0

    rows, records = [], []
    with tempfile.TemporaryDirectory() as td:
        for label, ck in (("nockpt", None), ("ckpt", Checkpointer(td))):
            ex = ElasticMeshExecutor(schedule, network=InstantNetwork(),
                                     checkpointer=ck)
            run_el = lambda: jax.block_until_ready(  # noqa: E731
                ex.run("delta", w0, data, eval_data, tau=tau).w_shared)
            run_el()  # compile (also warms every segment's program)
            t0 = time.perf_counter()
            res = ex.run("delta", w0, data, eval_data, tau=tau)
            jax.block_until_ready(res.w_shared)
            wall = time.perf_counter() - t0
            if ck is not None:
                ck.wait()
            resize_s = sum(e.wall_s for e in ex.resize_events)
            n_win = len(res.distortion)
            gap = (float(res.distortion[-1])
                   / float(res_fixed.distortion[-1]) - 1.0)
            rows.append(
                f"elastic_{label}_M{m0},{wall * 1e6:.0f},"
                f"resize_s={resize_s:.4f}"
                f" resize_frac={resize_s / wall:.3f}"
                f" final_C_gap={gap:+.4f}")
            for e in ex.resize_events:
                rows.append(
                    f"elastic_{label}_event_w{e.window},{e.wall_s * 1e6:.0f},"
                    f"M{e.old_m}->{e.new_m} late_points={e.late_points}")
            records.append({
                "variant": label, "m0": m0, "n": n, "d": d, "kappa": kappa,
                "tau": tau, "wall_s": wall, "wall_s_fixed": wall_fixed,
                "resize_s_total": resize_s, "n_windows": n_win,
                "final_C": float(res.distortion[-1]),
                "final_C_fixed": float(res_fixed.distortion[-1]),
                "events": [{
                    "window": e.window, "old_m": e.old_m, "new_m": e.new_m,
                    "late_points": e.late_points, "wall_s": e.wall_s,
                    "checkpointed": e.checkpoint_step is not None,
                } for e in ex.resize_events],
            })
    with open(out_path, "w") as f:
        json.dump({"suite": "elastic", "devices": len(jax.devices()),
                   "backend": jax.default_backend(),
                   "results": records}, f, indent=1)
    rows.append(f"elastic_trajectories,0,wrote {out_path} "
                f"({len(records)} records)")
    return rows


def bench_serve(*, quick: bool = False,
                out_path: str = "BENCH_serve.json") -> list[str]:
    """The serving subsystem: what do micro-batching and the sharded lookup
    buy, and does a live hot-swap hold up?

      * ``unbatched``  — the naive serving loop: one ``vq_assign`` dispatch
        per single-vector query on ONE device (the pre-serving baseline).
      * ``lookup_M*``  — batched sharded lookup, one bm=128 block per
        device: rows/s at batch = M*128.  The headline ``speedup`` record
        is batched rows/s at max M over the unbatched 1-device figure.
      * ``service``    — the full micro-batching ``QuantizeService`` under
        saturating open-loop load: q/s, p50/p99 (queue-inclusive).
      * ``hotswap``    — a live ``ElasticMeshExecutor`` publishes codebooks
        mid-load: zero failed requests + monotone served versions.

    CPU wall numbers are a correctness/ratio harness, not TPU-indicative
    (same caveat as ``bench_vq_kernel``); the gate in check_regression
    compares the machine-normalized speedup, not absolute rows/s."""
    import threading

    from repro.data import synthetic
    from repro.engine import ElasticMeshExecutor, InstantNetwork, ResizeSchedule
    from repro.serve import (CodebookStore, QuantizeService, ShardedLookup,
                             run_load)

    d, kappa, bm = 32, 64, 128
    key = jax.random.PRNGKey(0)
    kw_, kz = jax.random.split(key)
    w = jax.random.normal(kw_, (kappa, d))
    rows_out, records = [], []

    n_dev = len(jax.devices())
    counts = sorted({1, n_dev} if quick else
                    {m for m in (1, 2, 4, 8) if m <= n_dev})

    # -- unbatched baseline: one dispatch per query on one device
    look1 = ShardedLookup(n_devices=1)
    n_single = 100 if quick else 400
    zs = jax.random.normal(kz, (n_single, 1, d))
    jax.block_until_ready(look1.assign(zs[0], w))  # compile
    t0 = time.perf_counter()
    for i in range(n_single):
        jax.block_until_ready(look1.assign(zs[i], w))
    wall = time.perf_counter() - t0
    unbatched_rps = n_single / wall
    rows_out.append(f"serve_unbatched_M1,{wall / n_single * 1e6:.0f},"
                    f"rows_per_s={unbatched_rps:.0f}")
    records.append({"kind": "unbatched", "m": 1, "kappa": kappa, "d": d,
                    "rows_per_call": 1, "rows_per_s": unbatched_rps})

    # -- batched sharded lookup: one bm block per device
    batched_rps = {}
    for m in counts:
        look = ShardedLookup(n_devices=m)
        batch = m * bm
        z = jax.random.normal(kz, (batch, d))
        us = _time_call(lambda: look.assign(z, w)[0], iters=20)
        batched_rps[m] = batch / us * 1e6
        rows_out.append(f"serve_lookup_M{m},{us:.0f},"
                        f"batch={batch} rows_per_s={batched_rps[m]:.0f}"
                        f" plan={look.plan(kappa, d)}")
        records.append({"kind": "lookup", "m": m, "kappa": kappa, "d": d,
                        "rows_per_call": batch, "us_per_call": us,
                        "rows_per_s": batched_rps[m]})

    m_max = max(counts)
    speedup = batched_rps[m_max] / unbatched_rps
    rows_out.append(f"serve_speedup,0,batched_M{m_max}_over_unbatched="
                    f"{speedup:.1f}x (acceptance bar: >= 4x)")
    records.append({"kind": "speedup", "m": m_max, "kappa": kappa, "d": d,
                    "speedup": speedup})

    # -- service level: micro-batcher + futures under saturating open load
    store = CodebookStore(w)
    n_req = 100 if quick else 400
    with QuantizeService(store, ShardedLookup(n_devices=m_max),
                         max_delay_s=2e-3) as service:
        rep = run_load(service, n_requests=n_req, d=d, rows_per_request=16,
                       network=InstantNetwork(), tick_s=0.0)
    rows_out.append(f"serve_service_M{m_max},0,qps={rep.qps:.0f}"
                    f" rows_per_s={rep.rows_per_s:.0f}"
                    f" p50_ms={rep.p50_ms:.2f} p99_ms={rep.p99_ms:.2f}"
                    f" fill={service.stats.mean_fill:.0f}")
    records.append({"kind": "service", "m": m_max, "kappa": kappa, "d": d,
                    "qps": rep.qps, "rows_per_s": rep.rows_per_s,
                    "p50_ms": rep.p50_ms, "p99_ms": rep.p99_ms,
                    "failed": rep.failed,
                    "mean_fill": service.stats.mean_fill})

    # -- hot swap under load: a live elastic trainer publishes mid-stream
    m_train = min(8, n_dev)
    n_pts = 200 if quick else 400
    data = synthetic.replicate_stream(kz, m_train, n=n_pts, d=d)
    w0 = synthetic.kmeanspp_init(kw_, data.reshape(-1, d), kappa)
    store = CodebookStore(w0)
    n_win = n_pts // 10
    ex = ElasticMeshExecutor(
        ResizeSchedule([(n_win // 2, max(1, m_train // 2)), (n_win, m_train)]),
        network=InstantNetwork(), on_window=store.publisher(),
        publish_every=2)
    ex.run("delta", w0, data, data[:, :100], tau=10)  # compile warm-up
    store = CodebookStore(w0)
    ex.on_window = store.publisher()
    with QuantizeService(store, ShardedLookup(n_devices=m_max),
                         max_delay_s=1e-3) as service:
        trainer = threading.Thread(target=lambda: ex.run(
            "delta", w0, data, data[:, :100], tau=10))
        trainer.start()
        rep = run_load(service, n_requests=n_req, d=d, rows_per_request=4,
                       network=InstantNetwork(), tick_s=1.5e-3)
        trainer.join()
    rows_out.append(
        f"serve_hotswap,0,failed={rep.failed}"
        f" versions={rep.versions_min}..{rep.versions_max}"
        f" monotonic={rep.versions_monotonic}"
        f" published={store.version} staleness_max={rep.staleness_max}")
    records.append({"kind": "hotswap", "m": m_max, "kappa": kappa, "d": d,
                    "failed": rep.failed,
                    "versions_monotonic": rep.versions_monotonic,
                    "versions_served": [rep.versions_min, rep.versions_max],
                    "published": store.version,
                    "staleness_max": rep.staleness_max})

    with open(out_path, "w") as f:
        json.dump({"suite": "serve", "devices": n_dev,
                   "backend": jax.default_backend(),
                   "results": records}, f, indent=1)
    rows_out.append(f"serve_records,0,wrote {out_path} "
                    f"({len(records)} records)")
    return rows_out


def bench_comm(*, quick: bool = False,
               out_path: str = "BENCH_comm.json") -> list[str]:
    """Scheme x transport through the comm layer: wall clock + MEASURED
    merge wire bytes (from the transport's CommRecord stream) per cell.

      * ``cell``            — one (scheme, transport) run: best-of-3 wall,
        per-worker merge wire/logical bytes, final distortion.
      * ``sparse_reduction``— min over displacement schemes of the dense
        (xla) wire over the sparse wire at k/kappa = 0.25.  Machine-
        independent (bytes are trace-exact); acceptance bar >= 4x.
      * ``ring_parity``     — per-scheme ring/xla wall ratios.  On CPU
        meshes the ring transport falls back to the XLA collectives, so
        parity ~1 is the contract; on TPU this measures the Pallas ring
        against the stock collective.  The gate takes the MINIMUM
        regression over the scheme legs (engine-gate precedent: noise on
        an oversubscribed host hits single legs, a real ring slowdown
        hits all of them).

    CPU wall numbers are a correctness/ratio harness, not TPU-indicative
    (same caveat as ``bench_vq_kernel``).  The sweep itself lives in
    ``repro.comm.sweep`` — one definition shared with ``launch/dryrun.py
    --comm``, so the CI gate and the dry-run report cannot drift apart."""
    from repro.comm import sweep

    # best-of-3: single runs too noisy to gate
    cells = sweep.run_comm_cells(n=(200 if quick else 400), repeats=3)
    m, kappa, d = cells[0]["m"], cells[0]["kappa"], cells[0]["d"]
    sparse_frac = next(c["sparse_frac"] for c in cells
                       if c["transport"] == "sparse")
    rows, records = [], []
    for c in cells:
        rows.append(
            f"comm_{c['scheme']}_{c['transport']},{c['wall_s'] * 1e6:.0f},"
            f"merge_wire_B={c['merge_wire_bytes']}"
            f" logical_B={c['merge_logical_bytes']}"
            f" final_C={c['final_C']:.5f}")
        records.append({"kind": "cell", **{k: c[k] for k in (
            "scheme", "transport", "m", "n", "d", "kappa", "tau",
            "sparse_frac", "wall_s", "merge_wire_bytes",
            "merge_logical_bytes", "final_C")}})

    # compression applies to displacement merges ('average' ships means,
    # dense on every transport), so the reduction is min'd over those
    reduction = sweep.sparse_reduction(cells)
    parity = sweep.ring_parity(cells)
    rows.append(f"comm_sparse_reduction,0,xla_over_sparse_wire="
                f"{reduction:.2f}x (bar: >= 4x at k/kappa = 0.25)")
    rows.append("comm_ring_parity,0,ring_over_xla_wall="
                + " ".join(f"{s}={p:.2f}x" for s, p in parity.items()))
    records.append({"kind": "sparse_reduction", "m": m, "kappa": kappa,
                    "d": d, "sparse_frac": sparse_frac,
                    "reduction": reduction})
    records.append({"kind": "ring_parity", "m": m, "parity": parity})

    with open(out_path, "w") as f:
        json.dump({"suite": "comm", "devices": len(jax.devices()),
                   "backend": jax.default_backend(),
                   "results": records}, f, indent=1)
    rows.append(f"comm_records,0,wrote {out_path} ({len(records)} records)")
    return rows


def bench_hier(*, quick: bool = False,
               out_path: str = "BENCH_hier.json") -> list[str]:
    """Flat vs hierarchical execution: every scheme through the flat
    8-worker mesh and the 2x4 two-tier topology (dense and sparse tier 1),
    wall clock + MEASURED per-tier merge wire bytes per cell.

      * ``cell``            — one (scheme, variant) run: best-of-3 wall,
        per-worker merge wire split into tier 0 (intra-host) and tier 1
        (inter-host) from the per-tier ``CommRecord``s, final distortion,
        and — for the hierarchical variants — whether the run bit-matched
        the flat reference (``bitmatch_flat``; dense tier 1 MUST, that is
        the tentpole's oracle-equivalence contract).
      * ``inter_reduction`` — min over displacement schemes of the dense
        tier-1 wire over the sparse tier-1 wire.  Machine-independent
        (bytes are trace-exact); acceptance bar >= 4x at k/kappa = 0.25.
      * ``hier_parity``     — per-scheme hier-dense/flat wall ratios (same
        box, machine divides out; the gate takes the min regression over
        schemes, the engine-gate flap-proof statistic).

    CPU wall numbers are a correctness/ratio harness, not TPU-indicative.
    The sweep lives in ``repro.comm.sweep`` — one definition shared with
    ``launch/dryrun.py --comm``'s hier table."""
    from repro.comm import sweep

    cells = sweep.run_hier_cells(n=(200 if quick else 400), repeats=3)
    hier = [c for c in cells if c["variant"] != "flat"]
    tier1_frac = next(c["tier1_frac"] for c in cells
                     if c["variant"] == "hier_sparse")
    rows, records = [], []
    for c in cells:
        extra = ("" if c["variant"] == "flat"
                 else f" bitmatch_flat={c['bitmatch_flat']}")
        rows.append(
            f"hier_{c['scheme']}_{c['variant']},{c['wall_s'] * 1e6:.0f},"
            f"intra_wire_B={c['tier0_wire_bytes']}"
            f" inter_wire_B={c['tier1_wire_bytes']}"
            f" final_C={c['final_C']:.5f}{extra}")
        records.append({"kind": "cell", **c})

    reduction = sweep.hier_inter_reduction(cells)
    parity = sweep.hier_wall_parity(cells)
    dense_bitmatch = all(c["bitmatch_flat"] for c in hier
                         if c["variant"] == "hier_dense")
    rows.append(f"hier_inter_reduction,0,dense_over_sparse_tier1_wire="
                f"{reduction:.2f}x (bar: >= 4x at k/kappa = 0.25)")
    rows.append(f"hier_dense_bitmatch,0,all_schemes={dense_bitmatch}")
    rows.append("hier_wall_parity,0,hier_dense_over_flat_wall="
                + " ".join(f"{s}={p:.2f}x" for s, p in parity.items()))
    records.append({"kind": "inter_reduction",
                    "m": cells[0]["m"], "hosts": hier[0]["hosts"],
                    "kappa": cells[0]["kappa"], "d": cells[0]["d"],
                    "tier1_frac": tier1_frac, "reduction": reduction,
                    "dense_bitmatch": dense_bitmatch})
    records.append({"kind": "hier_parity", "m": cells[0]["m"],
                    "parity": parity})

    with open(out_path, "w") as f:
        json.dump({"suite": "hier", "devices": len(jax.devices()),
                   "backend": jax.default_backend(),
                   "results": records}, f, indent=1)
    rows.append(f"hier_records,0,wrote {out_path} ({len(records)} records)")
    return rows


def bench_obs(*, quick: bool = False,
              out_path: str = "BENCH_obs.json") -> list[str]:
    """What does LIVE instrumentation cost?  Every scheme through the
    8-worker mesh twice — bare vs a live ``Tracer`` + ``MetricsRegistry``
    (enabled but unexported, the always-on configuration) — plus one
    traced 2-host hierarchical run pushed through the trace-invariant
    checker.

      * ``overhead`` — per scheme: N interleaved off/on pairs on
        identical seeded runs (A/B alternation so machine drift lands on
        both sides).  Two noise-robust estimators are computed — the
        best-of-N ratio min(on)/min(off) and the median of the per-pair
        on/off ratios — and the recorded overhead is the SMALLER: host
        noise is one-sided (it only ever adds time) and hits the two
        estimators through different failure modes (a single quiet
        sample repairs the min; drift cancellation repairs the median),
        while a genuine instrumentation cost inflates both.  Raw
        per-iteration samples are recorded so the gate can see the
        noise floor.  Acceptance bar: <= 1.03x (instrumentation < 3%).
      * ``trace`` — a 2-host hierarchical delta run with the tracer on:
        the exported Chrome events must pass ``repro.obs.check_trace``
        with tier-0 AND tier-1 merge spans and the per-window
        ``codebook_divergence`` counter present (the ``launch.train
        --hosts 2 --trace`` acceptance criterion, run in-process).

    The overhead ratio is same-box (machine divides out); absolute CPU
    walls are a harness, not TPU-indicative (``bench_vq_kernel`` caveat).
    """
    from repro import comm
    from repro.data import synthetic
    from repro.engine import InstantNetwork, MeshExecutor
    from repro.obs import MetricsRegistry, Tracer, check_trace
    from repro.topology import Topology

    # n large enough that per-window compute amortizes the fixed
    # per-window emission cost (span count scales with windows, not
    # points); quick mode halves tau, which scales wall time without
    # moving the emission/compute ratio
    m, n, d, kappa, tau = 8, 4000, 8, 16, (50 if quick else 100)
    m = min(m, len(jax.devices()))
    repeats = 5 if quick else 9
    key = jax.random.PRNGKey(0)
    kd, kw, ka = jax.random.split(key, 3)
    data = synthetic.replicate_stream(kd, m, n=n, d=d)
    eval_data = data[:, : min(200, n)]
    w0 = synthetic.kmeanspp_init(kw, data.reshape(-1, d), kappa)

    rows, records = [], []
    for scheme in ("average", "delta", "async_delta"):
        bare = MeshExecutor(network=InstantNetwork())
        live = MeshExecutor(network=InstantNetwork(), tracer=Tracer(),
                            metrics=MetricsRegistry())
        for ex in (bare, live):  # the observe flag keys a distinct program
            jax.block_until_ready(
                ex.run(scheme, w0, data, eval_data, tau=tau,
                       key=ka).w_shared)
        samples: dict[str, list[float]] = {"off": [], "on": []}
        for _ in range(repeats):
            for label, ex in (("off", bare), ("on", live)):
                t0 = time.perf_counter()
                res = ex.run(scheme, w0, data, eval_data, tau=tau, key=ka)
                jax.block_until_ready(res.w_shared)
                samples[label].append(time.perf_counter() - t0)
        min_ratio = min(samples["on"]) / min(samples["off"])
        pair_ratios = sorted(on / off for on, off
                             in zip(samples["on"], samples["off"]))
        median_pair = pair_ratios[len(pair_ratios) // 2]
        overhead = min(min_ratio, median_pair)
        n_spans = len(live.tracer.spans())
        rows.append(f"obs_overhead_{scheme},"
                    f"{min(samples['on']) * 1e6:.0f},"
                    f"on_over_off={overhead:.3f}x (bar <= 1.03x)"
                    f" min_ratio={min_ratio:.3f} median_pair="
                    f"{median_pair:.3f} spans={n_spans}")
        records.append({
            "kind": "overhead", "scheme": scheme, "m": m, "n": n, "d": d,
            "kappa": kappa, "tau": tau, "repeats": repeats,
            "wall_s_off": min(samples["off"]),
            "wall_s_on": min(samples["on"]),
            "wall_samples_off": samples["off"],
            "wall_samples_on": samples["on"],
            "overhead": overhead, "min_ratio": min_ratio,
            "median_pair": median_pair, "spans": n_spans})

    # -- traced 2-host hierarchical run -> invariant checker
    hosts = min(2, m)
    topo = Topology.from_spec(m, hosts=hosts)
    tracer, registry = Tracer(), MetricsRegistry()
    ex = MeshExecutor(topology=topo, network=InstantNetwork(),
                      transport=comm.HierarchicalTransport(
                          tier0="xla", tier1="xla",
                          host_axis=topo.host_axis,
                          worker_axis=topo.worker_axis),
                      tracer=tracer, metrics=registry)
    jax.block_until_ready(
        ex.run("delta", w0, data, eval_data, tau=tau, key=ka).w_shared)
    events = tracer.chrome_events()
    errors = check_trace(
        events, expect_merge_tiers={"0", "1"},
        expect_counters=["codebook_divergence", "distortion"])
    trace_ok = not errors
    n_spans = sum(1 for e in events if e.get("ph") == "X")
    rows.append(f"obs_trace_hier,0,ok={trace_ok} spans={n_spans} hosts="
                f"{hosts}" + ("" if trace_ok
                              else " errors=" + "; ".join(errors[:3])))
    records.append({
        "kind": "trace", "m": m, "hosts": hosts, "n": n, "d": d,
        "kappa": kappa, "tau": tau, "trace_ok": trace_ok,
        "n_spans": n_spans, "errors": errors})

    with open(out_path, "w") as f:
        json.dump({"suite": "obs", "devices": len(jax.devices()),
                   "backend": jax.default_backend(),
                   "results": records}, f, indent=1)
    rows.append(f"obs_records,0,wrote {out_path} ({len(records)} records)")
    return rows


def bench_chaos(*, quick: bool = False, out_path: str = "BENCH_chaos.json",
                seed: int = 7) -> list[str]:
    """Survive the cloud the paper ran on: a seeded kill/slow/partition
    schedule (2 worker deaths -> unscheduled elastic resizes, 1 straggler +
    1 host-group partition -> quorum-merge late folds) against the
    fault-free fixed-M oracle on the SAME sample budget.

      * ``chaos``  — the faulted run: final distortion over the oracle's
        (``distortion_ratio``, the acceptance bound), quorum-merge wire
        bytes (masked collective, trace-exact), recovery wall cost (the
        summed kill-resize pauses), and the full event schedule (the
        seeded-determinism pin: same seed => byte-identical events on
        every device count).
      * ``trace``  — the tracer ran live during the chaos run; the
        exported events must pass ``check_trace`` with the ``chaos_*``
        spans and the late-worker counter present.

    CPU wall numbers are a harness, not TPU-indicative; the gate pins the
    machine-independent quantities (events, wire bytes, distortion ratio).
    """
    from repro.data import synthetic
    from repro.engine import (ChaosNetwork, ChaosSchedule,
                              ElasticMeshExecutor, InstantNetwork,
                              MeshExecutor, ResizeSchedule)
    from repro.obs import MetricsRegistry, Tracer, check_trace

    n, d, kappa, tau = (400 if quick else 800), 8, 16, 10
    m = min(8, len(jax.devices()))
    hosts, quorum_frac = 2, 0.6
    kills = min(2, m - 1)
    schedule = ChaosSchedule.generate(
        seed, windows=n // tau, m=m, kills=kills, slows=1, partitions=1,
        hosts=hosts)
    key = jax.random.PRNGKey(0)
    kd, kw = jax.random.split(key)
    data = synthetic.replicate_stream(kd, m, n=n, d=d)
    eval_data = data[:, :200]
    w0 = synthetic.kmeanspp_init(kw, data.reshape(-1, d), kappa)

    # fault-free oracle: the fixed-M delta run on the same sample budget
    oracle = MeshExecutor(network=InstantNetwork())
    run_o = lambda: jax.block_until_ready(  # noqa: E731
        oracle.run("delta", w0, data, eval_data, tau=tau).w_shared)
    run_o()  # compile
    res_o = oracle.run("delta", w0, data, eval_data, tau=tau)
    jax.block_until_ready(res_o.w_shared)

    tracer, registry = Tracer(), MetricsRegistry()
    net = ChaosNetwork(InstantNetwork(), schedule)
    ex = ElasticMeshExecutor(ResizeSchedule([]), network=net, chaos=schedule,
                             merge="quorum", quorum_frac=quorum_frac,
                             tracer=tracer, metrics=registry)
    jax.block_until_ready(
        ex.run("delta", w0, data, eval_data, tau=tau).w_shared)  # compile
    tracer, registry = Tracer(), MetricsRegistry()
    ex.tracer = tracer
    ex.metrics = registry
    for mex in ex._mesh_ex.values():
        mex.tracer, mex.metrics = tracer, registry
    t0 = time.perf_counter()
    res = ex.run("delta", w0, data, eval_data, tau=tau)
    jax.block_until_ready(res.w_shared)
    wall_s = time.perf_counter() - t0
    recovery_s = sum(e.wall_s for e in ex.resize_events
                     if e.cause == "chaos_kill")
    merge_b = ex.last_comm["by_tag"].get("merge", {"wire_bytes": 0,
                                                   "logical_bytes": 0})
    final_c = float(res.distortion[-1])
    final_o = float(res_o.distortion[-1])
    ratio = final_c / final_o

    events = tracer.chrome_events()
    expect = [f"chaos_{e.kind}" for e in schedule]
    errors = check_trace(events, expect_spans=sorted(set(expect)))
    trace_ok = not errors
    trace_path = os.path.splitext(out_path)[0] + ".trace.json"
    tracer.export_chrome(trace_path)

    rows = [
        f"chaos_seed{seed}_M{m},{wall_s * 1e6:.0f},"
        f"distortion_ratio={ratio:.4f} final_C={final_c:.5f}"
        f" oracle_C={final_o:.5f} kills={kills}"
        f" recovery_s={recovery_s:.4f}",
        f"chaos_merge_wire,0,wire_B={merge_b['wire_bytes']}"
        f" logical_B={merge_b['logical_bytes']}",
        f"chaos_schedule,0,{schedule.describe()}",
        f"chaos_trace,0,ok={trace_ok} -> {trace_path}"
        + ("" if trace_ok else " errors=" + "; ".join(errors[:3])),
    ]
    records = [{
        "kind": "chaos",
        "seed": seed, "m": m, "n": n, "d": d, "kappa": kappa, "tau": tau,
        "hosts": hosts, "quorum_frac": quorum_frac,
        "events": [e.as_dict() for e in schedule],
        "final_C": final_c, "final_C_oracle": final_o,
        "distortion_ratio": ratio,
        "merge_wire_bytes": merge_b["wire_bytes"],
        "merge_logical_bytes": merge_b["logical_bytes"],
        "wall_s": wall_s, "recovery_wall_s": recovery_s,
        "resizes": [{"window": e.window, "old_m": e.old_m,
                     "new_m": e.new_m, "cause": e.cause,
                     "late_points": e.late_points,
                     "wall_s": e.wall_s} for e in ex.resize_events],
        "trace_ok": trace_ok, "trace_errors": errors,
    }]
    with open(out_path, "w") as f:
        json.dump({"suite": "chaos", "devices": len(jax.devices()),
                   "backend": jax.default_backend(),
                   "results": records}, f, indent=1)
    rows.append(f"chaos_records,0,wrote {out_path} ({len(records)} records)")
    return rows


def bench_profile(*, quick: bool = False,
                  out_path: str = "BENCH_profile.json") -> list[str]:
    """Where does the wall go?  Every scheme through the 8-worker mesh with
    a live ``Profiler``: measured wall decomposed per window against the
    three-term roofline (analytic VQ compute/HBM + collective bytes from
    the compiled program's HLO) plus the host residual.

      * ``attribution`` — per scheme: the best (min-wall) warm run's
        attribution record.  Acceptance: the terms (residual included) sum
        to the measured window wall within 15% — the residual is clamped
        at zero, so the check fails exactly when the modeled terms
        OVERSHOOT measured wall, i.e. when an analytic count or a trip
        count is wrong.  ``collective_bytes_per_window`` is parsed from
        the compiled HLO with trip-count correction, so it is machine-
        independent and pinned EXACTLY by the gate; it is also
        cross-checked here against the transport's own ``CommLog``
        logical-byte accounting of the same program.

    Efficiency gauges are TPU-v5e-relative; on the CPU CI harness they
    are tiny (the host term dominates) — the compute-efficiency floor
    gate only pins that the analytic terms are nonzero and attributed.
    """
    from repro.data import synthetic
    from repro.engine import InstantNetwork, MeshExecutor
    from repro.obs import MetricsRegistry, Profiler

    m, n, d, kappa, tau = 8, (2000 if quick else 4000), 8, 16, 50
    m = min(m, len(jax.devices()))
    repeats = 3 if quick else 5
    key = jax.random.PRNGKey(0)
    kd, kw, ka = jax.random.split(key, 3)
    data = synthetic.replicate_stream(kd, m, n=n, d=d)
    eval_data = data[:, : min(200, n)]
    w0 = synthetic.kmeanspp_init(kw, data.reshape(-1, d), kappa)

    rows, records = [], []
    for scheme in ("average", "delta", "async_delta"):
        registry = MetricsRegistry()
        prof = Profiler(metrics=registry)
        ex = MeshExecutor(network=InstantNetwork(), profiler=prof,
                          metrics=registry)
        jax.block_until_ready(
            ex.run(scheme, w0, data, eval_data, tau=tau,
                   key=ka).w_shared)           # compile (AOT + HLO parse)
        for _ in range(repeats):
            jax.block_until_ready(
                ex.run(scheme, w0, data, eval_data, tau=tau,
                       key=ka).w_shared)
        warm = [a for a in prof.attributions if not a["compiled_in_run"]]
        best = min(warm, key=lambda a: a["wall_s"])
        # CommLog ground truth for the same program: every all-reduce the
        # HLO carries per window is a merge- or eval-tagged logical payload
        by_tag = ex.last_comm["by_tag"]
        log_pw = sum(t["logical_bytes"] for t in by_tag.values()) \
            / best["n_windows"]
        eff = best["efficiency"]
        rows.append(
            f"profile_{scheme},{best['wall_s'] * 1e6:.0f},"
            f"consistency={best['consistency']:.4f} (bar <= 0.15)"
            f" coll_B_per_window={best['collective_bytes_per_window']:.1f}"
            f" commlog_B={log_pw:.1f}"
            f" host%={eff['host'] * 100:.1f}")
        records.append({
            "kind": "attribution", "scheme": scheme,
            "transport": ex.transport.name, "m": m, "n": n, "d": d,
            "kappa": kappa, "tau": tau, "repeats": repeats,
            "wall_s": best["wall_s"],
            "commlog_logical_bytes_per_window": log_pw,
            "attribution": best})

    with open(out_path, "w") as f:
        json.dump({"suite": "profile", "devices": len(jax.devices()),
                   "backend": jax.default_backend(),
                   "results": records}, f, indent=1)
    rows.append(f"profile_records,0,wrote {out_path} "
                f"({len(records)} records)")
    return rows


def bench_adapt(*, quick: bool = False,
                out_path: str = "BENCH_adapt.json") -> list[str]:
    """Adaptive communication: divergence-triggered merges + quantized
    wire vs the fixed-tau frontier, on one workload.

      * ``cell``       — one (merge, quant) run from the shared
        ``sweep.run_adapt_cells`` grid ({fixed, dynamic} x {dense, bf16,
        int8}): best-of-3 wall, measured merge + probe wire bytes, how
        many of the windows actually triggered, final distortion.
      * ``fixed_leg``  — plain delta-merge legs across tau in (5, 10, 20):
        the fixed-tau frontier the dynamic merge is gated against.
      * ``adapt_summary`` — the acceptance predicates in one record: the
        thresh=0/quant-off run bit-matches the plain delta merge
        (``bitmatch``), and the dynamic-dense and dynamic-int8 cells land
        within rtol 1e-2 of the BEST fixed-tau leg's final distortion at
        strictly fewer total wire bytes.

    Wire bytes and trigger counts are trace-exact and seeded, so the gate
    pins them EXACTLY; only wall rides ratios."""
    from repro.comm import sweep

    n = 160 if quick else 240
    cells = sweep.run_adapt_cells(n=n, repeats=3)
    legs = sweep.run_fixed_tau_legs(n=n)
    bitmatch = sweep.adapt_bitmatch(n=n)
    best = sweep.best_fixed_leg(legs)

    rows, records = [], []
    for c in cells:
        rows.append(
            f"adapt_{c['merge']}_{c['quant']},{c['wall_s'] * 1e6:.0f},"
            f"wire_B={c['total_wire_bytes']}"
            f" trig={c['n_triggered']}/{c['n_windows']}"
            f" final_C={c['final_C']:.5f}")
        records.append({"kind": "cell", **{k: c[k] for k in (
            "merge", "quant", "m", "n", "d", "kappa", "tau", "thresh",
            "max_stale", "wall_s", "merge_wire_bytes", "probe_wire_bytes",
            "total_wire_bytes", "n_windows", "n_triggered", "final_C")}})
    for leg in legs:
        rows.append(f"adapt_fixed_tau{leg['tau']},0,"
                    f"wire_B={leg['total_wire_bytes']}"
                    f" final_C={leg['final_C']:.5f}")
        records.append({"kind": "fixed_leg", **leg})

    dyn = {c["quant"]: c for c in cells if c["merge"] == "dynamic"}
    summary = {
        "kind": "adapt_summary", "bitmatch": bitmatch,
        "best_tau": best["tau"], "best_final_C": best["final_C"],
        "best_wire_bytes": best["total_wire_bytes"],
        "dyn_dense_final_C": dyn["dense"]["final_C"],
        "dyn_dense_wire_bytes": dyn["dense"]["total_wire_bytes"],
        "dyn_int8_final_C": dyn["int8"]["final_C"],
        "dyn_int8_wire_bytes": dyn["int8"]["total_wire_bytes"],
        "dynamic_wire_ok": sweep.adapt_dynamic_wire_ok(cells),
    }
    records.append(summary)
    rows.append(
        f"adapt_summary,0,bitmatch={bitmatch}"
        f" best_tau={best['tau']} best_C={best['final_C']:.5f}"
        f" dyn_C={summary['dyn_dense_final_C']:.5f}"
        f" dyn_wire={summary['dyn_dense_wire_bytes']}"
        f"/{summary['best_wire_bytes']}B")

    with open(out_path, "w") as f:
        json.dump({"suite": "adapt", "devices": len(jax.devices()),
                   "backend": jax.default_backend(),
                   "results": records}, f, indent=1)
    rows.append(f"adapt_records,0,wrote {out_path} ({len(records)} records)")
    return rows


BENCHES = {
    "fig1": bench_fig1,
    "fig2": bench_fig2,
    "fig3": bench_fig3,
    "fig4": bench_fig4,
    "vq_kernel": bench_vq_kernel,
    "merge": bench_merge_strategies,
    "throughput": bench_training_throughput,
    "decode": bench_decode_throughput,
    "engine": bench_engine,
    "elastic": bench_elastic,
    "serve": bench_serve,
    "comm": bench_comm,
    "hier": bench_hier,
    "obs": bench_obs,
    "chaos": bench_chaos,
    "profile": bench_profile,
    "adapt": bench_adapt,
}

# named groups runnable as `--suite NAME`
SUITES = {
    "engine": ["engine"],
    "elastic": ["elastic"],
    "serve": ["serve"],
    "comm": ["comm"],
    "hier": ["hier"],
    "obs": ["obs"],
    "chaos": ["chaos"],
    "profile": ["profile"],
    "adapt": ["adapt"],
    "paper": ["fig1", "fig2", "fig3", "fig4"],
    "lm": ["throughput", "decode"],
}

# benches that take (quick, out_path) and write a JSON record
_JSON_BENCHES = {"engine": "BENCH_engine.json",
                 "elastic": "BENCH_elastic.json",
                 "serve": "BENCH_serve.json",
                 "comm": "BENCH_comm.json",
                 "hier": "BENCH_hier.json",
                 "obs": "BENCH_obs.json",
                 "chaos": "BENCH_chaos.json",
                 "profile": "BENCH_profile.json",
                 "adapt": "BENCH_adapt.json"}


def suite_out_path(out: str, name: str, *, multi: bool) -> str:
    """Output path for one JSON suite under ``--out``.

    With one JSON suite selected, ``--out`` is used verbatim.  With several,
    each suite gets a derived sibling path — ``--out FRESH.json`` writes
    ``FRESH.engine.json``, ``FRESH.elastic.json``, ... — instead of the old
    behaviour of warning and ignoring ``--out`` entirely."""
    if not out:
        return _JSON_BENCHES[name]
    if not multi:
        return out
    base, ext = os.path.splitext(out)
    return f"{base}.{name}{ext or '.json'}"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=sorted(BENCHES))
    ap.add_argument("--suite", choices=sorted(SUITES))
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--chaos-seed", type=int, default=7,
                    help="chaos suite: seed the kill/slow/partition "
                         "schedule is drawn from (the cron sweep matrixes "
                         "over this)")
    ap.add_argument("--out", default="",
                    help="JSON output path for the engine/elastic/serve "
                         "suites (default: the committed BENCH_<name>.json "
                         "baseline path; CI writes a fresh file and diffs "
                         "against the baseline with "
                         "benchmarks.check_regression).  When several JSON "
                         "suites are selected, each gets a derived sibling "
                         "path: --out F.json -> F.engine.json, ...")
    args = ap.parse_args()
    compile_cache.enable()
    if args.only:
        names = [args.only]
    elif args.suite:
        names = SUITES[args.suite]
    else:
        names = list(BENCHES)
    if args.quick:
        names = [n for n in names if n not in ("fig4",)]
    json_names = [n for n in names if n in _JSON_BENCHES]
    multi = len(json_names) > 1
    if args.out and multi:
        outs = {n: suite_out_path(args.out, n, multi=True)
                for n in json_names}
        print(f"note: --out covers {len(json_names)} JSON suites; writing "
              + ", ".join(f"{n} -> {p}" for n, p in outs.items()))
    print("name,us_per_call,derived")
    for name in names:
        kwargs = {}
        if name in _JSON_BENCHES:
            kwargs = {"quick": args.quick,
                      "out_path": suite_out_path(args.out, name,
                                                 multi=multi)}
            if name == "chaos":
                kwargs["seed"] = args.chaos_seed
        try:
            for row in BENCHES[name](**kwargs):
                print(row)
        except Exception as e:  # noqa: BLE001
            print(f"{name},0,ERROR:{type(e).__name__}:{e}")


if __name__ == "__main__":
    main()
