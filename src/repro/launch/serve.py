"""Serving drivers — the LM decode smoke and the VQ quantization service.

LM mode (default): continuous-batching style loop over request waves —
prefill each wave once, decode to completion, report throughput.

    PYTHONPATH=src python -m repro.launch.serve --arch granite_8b --smoke \
        --waves 3 --batch 4 --prompt 16 --gen 16

VQ mode: the online quantization service end to end — a ``CodebookStore``
fed by a background training run (hot-swapping codebooks mid-load when
``--train-publish`` is set), a micro-batching ``QuantizeService`` over the
sharded lookup engine, and an open-loop load generator with the paper's
cloud arrival process:

    PYTHONPATH=src python -m repro.launch.serve --mode vq --requests 500 \
        --kappa 64 --dim 32 [--network geometric --p-delay 0.5] \
        [--train-publish] [--smoke]
"""

from __future__ import annotations

import argparse
import threading
import time

import jax
import jax.numpy as jnp

from repro import compile_cache


def run_vq(args) -> int:
    """Drive the quantization service: store -> service -> load -> report."""
    from repro.data import synthetic
    from repro.engine import (ElasticMeshExecutor, InstantNetwork,
                              ResizeSchedule, get_network)
    from repro.obs import MetricsRegistry, Tracer
    from repro.serve import (CodebookStore, QuantizeService, ShardedLookup,
                             run_load)

    tracer = Tracer() if (args.trace or args.metrics) else None
    metrics = MetricsRegistry() if (args.trace or args.metrics) else None
    if args.smoke:
        args.requests = min(args.requests, 100)
        args.points = min(args.points, 200)
        if args.train_publish:
            # stretch the smoke load across several training windows so the
            # monotonic-versions check actually sees hot swaps mid-load
            args.tick_ms = max(args.tick_ms, 4.0)
    key = jax.random.PRNGKey(args.seed)
    kd, kw, ka = jax.random.split(key, 3)
    n_dev = len(jax.devices())
    m_train = min(8, n_dev)
    # the initial codebook samples kappa distinct points, so draw at least
    # that many even when --points (the trainer's stream) is shorter
    pool = synthetic.replicate_stream(
        kd, m_train, n=max(args.points, -(-args.kappa // m_train)),
        d=args.dim)
    data = pool[:, :args.points]
    w0 = synthetic.kmeanspp_init(kw, pool.reshape(-1, args.dim), args.kappa)

    net_kw = {}
    if args.network == "fixed":
        net_kw["latency_ticks"] = args.latency
    elif args.network == "geometric":
        net_kw["p_delay"] = args.p_delay
    network = get_network(args.network, **net_kw)

    store = CodebookStore(w0)
    lookup = ShardedLookup(n_devices=n_dev)
    plan = lookup.plan(args.kappa, args.dim)
    print(f"serve: devices={n_dev} plan={plan} "
          f"max_batch={lookup.n_shards * 128} "
          f"max_delay={args.max_delay_ms}ms network={args.network}"
          + (" train-publish" if args.train_publish else ""))

    trainer = None
    trainer_err: list[Exception] = []
    if args.train_publish:
        # a live elastic training run publishes into the store mid-load:
        # grow/shrink the worker set AND hot-swap the served codebook
        n_windows = args.points // args.tau
        schedule = ResizeSchedule(
            [(max(1, n_windows // 3), max(1, m_train // 2)),
             (max(2, 2 * n_windows // 3), m_train)])
        ex = ElasticMeshExecutor(schedule, network=InstantNetwork(),
                                 on_window=store.publisher(),
                                 publish_every=args.publish_every,
                                 tracer=tracer, metrics=metrics)
        eval_data = data[:, : min(100, args.points)]

        def train():
            try:
                ex.run("delta", w0, data, eval_data, tau=args.tau)
            except Exception as e:  # noqa: BLE001 — reported after the load
                trainer_err.append(e)

        trainer = threading.Thread(target=train, name="train-publish")

    t0 = time.perf_counter()
    with QuantizeService(store, lookup,
                         max_delay_s=args.max_delay_ms * 1e-3,
                         tracer=tracer) as service:
        if trainer is not None:
            trainer.start()
            # don't let the load race the trainer's compile: wait for the
            # first fresh publication so the requests actually overlap the
            # remaining hot-swaps (otherwise the monotonic-versions exit
            # check below would only ever see version 1)
            if not store.wait_for(2, timeout=300.0):
                print("error: trainer never published a codebook")
                return 1
        report = run_load(service, n_requests=args.requests, d=args.dim,
                          rows_per_request=args.rows, network=network,
                          tick_s=args.tick_ms * 1e-3, key=ka,
                          tracer=tracer, metrics=metrics)
        if trainer is not None:
            trainer.join()
    wall = time.perf_counter() - t0

    print(report.summary())
    st = service.stats
    if metrics is not None:
        # the service keeps its counters in ServiceStats; copy them in
        # once, now that the flush thread has stopped
        for kind in ("full", "deadline"):
            metrics.counter("serve_flushes", kind=kind).inc(
                getattr(st, f"{kind}_flushes"))
        metrics.counter("serve_rows").inc(st.rows)
        metrics.counter("serve_padded_rows").inc(st.padded_rows)
        metrics.counter("serve_failed").inc(st.failed)
    print(f"flushes={st.flushes} (full={st.full_flushes} "
          f"deadline={st.deadline_flushes}) mean_fill={st.mean_fill:.1f} "
          f"rows/flush, padded_rows={st.padded_rows}")
    if trainer is not None:
        print(f"trainer published {store.version} codebook versions "
              f"(served {report.versions_min}..{report.versions_max}, "
              f"max staleness {report.staleness_max})")
    print(f"done in {wall:.2f}s wall")
    if metrics is not None:
        print("metrics:")
        print(metrics.summary_table())
    if args.trace:
        tracer.export_chrome(args.trace)
        print(f"trace: {len(tracer.spans())} spans -> {args.trace} "
              f"(load at https://ui.perfetto.dev)")
    if args.metrics:
        n_rows = metrics.dump_jsonl(args.metrics, run="serve-vq")
        print(f"metrics: {n_rows} rows appended -> {args.metrics}")
    if trainer_err:
        print(f"error: training thread failed: {trainer_err[0]}")
        return 1
    if report.failed:
        print(f"error: {report.failed} requests failed")
        return 1
    if not report.versions_monotonic:
        print("error: served codebook versions were not monotonic")
        return 1
    return 0


def run_lm(args) -> int:
    from repro.configs import registry
    from repro.launch.mesh import make_host_mesh
    from repro.models import common as model_common
    from repro.training import steps as steps_lib

    cfg = (registry.get_smoke_config(args.arch) if args.smoke
           else registry.get_config(args.arch))
    mesh = make_host_mesh()
    model_common.set_run_options(mesh=mesh)
    from repro.models.api import get_api
    api = get_api(cfg)
    key = jax.random.PRNGKey(0)
    params = api.init(key)
    max_len = args.prompt + args.gen
    prefill = jax.jit(steps_lib.make_prefill_step(cfg, max_len=max_len))
    serve = jax.jit(steps_lib.make_serve_step(cfg))

    total_tok, t0 = 0, time.perf_counter()
    with mesh:
        for wave in range(args.waves):
            prompts = jax.random.randint(
                jax.random.fold_in(key, wave),
                (args.batch, args.prompt), 0, cfg.vocab)
            batch = {"tokens": prompts}
            if cfg.family == "encdec":
                batch["frames"] = jax.random.normal(
                    jax.random.fold_in(key, 1000 + wave),
                    (args.batch, cfg.encoder_frames, cfg.d_model), cfg.dtype)
            if cfg.family == "vlm":
                batch["patch_embeds"] = jax.random.normal(
                    jax.random.fold_in(key, 2000 + wave),
                    (args.batch, cfg.img_tokens, cfg.d_model), cfg.dtype)
            logits, cache = prefill(params, batch)
            tok = jnp.argmax(logits.reshape(args.batch, -1), -1)[:, None]
            for _ in range(args.gen):
                logits, cache = serve(params, cache, tok)
                tok = jnp.argmax(logits[:, -1], -1)[:, None]
                total_tok += args.batch
            print(f"wave {wave}: generated {args.gen} tokens x "
                  f"{args.batch} requests")
    dt = time.perf_counter() - t0
    print(f"served {args.waves * args.batch} requests, "
          f"{total_tok} tokens in {dt:.1f}s ({total_tok / dt:,.0f} tok/s)")
    return 0


def main(argv=None) -> int:
    from repro.configs import registry

    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("lm", "vq"), default="lm")
    ap.add_argument("--arch", default="granite_8b",
                    choices=registry.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--waves", type=int, default=3)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    # VQ-mode options (--mode vq): service + load + optional live trainer
    ap.add_argument("--requests", type=int, default=500)
    ap.add_argument("--rows", type=int, default=1,
                    help="query vectors per request")
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--kappa", type=int, default=64)
    ap.add_argument("--max-delay-ms", type=float, default=2.0,
                    help="micro-batcher flush deadline")
    ap.add_argument("--network",
                    choices=("instant", "fixed", "geometric"),
                    default="geometric",
                    help="arrival process (geometric = paper cloud model)")
    ap.add_argument("--latency", type=int, default=1)
    ap.add_argument("--p-delay", type=float, default=0.5)
    ap.add_argument("--tick-ms", type=float, default=0.05,
                    help="seconds per arrival tick (0 = saturating)")
    ap.add_argument("--train-publish", action="store_true",
                    help="run an elastic training in the background, "
                         "hot-swapping the served codebook at windows")
    ap.add_argument("--publish-every", type=int, default=2,
                    help="training windows per codebook publication")
    ap.add_argument("--points", type=int, default=400,
                    help="training points per worker (--train-publish)")
    ap.add_argument("--tau", type=int, default=10)
    ap.add_argument("--trace", default="", metavar="OUT.json",
                    help="write a Chrome trace-event file (Perfetto): "
                         "flush spans, load spans, trainer windows")
    ap.add_argument("--metrics", default="", metavar="OUT.jsonl",
                    help="append the metrics registry (latency histogram, "
                         "flush/row counters) as JSONL")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.enable()

    if args.mode == "vq":
        return run_vq(args)
    return run_lm(args)


if __name__ == "__main__":
    raise SystemExit(main())
