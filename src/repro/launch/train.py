"""End-to-end training driver — LM training and the paper's VQ schemes.

LM mode (default):

    PYTHONPATH=src python -m repro.launch.train --arch granite_8b --smoke \
        --steps 200 --ckpt-dir /tmp/ckpt [--resume]

VQ mode — the paper's workload through the ``repro.engine`` Executor API,
on any of the three backends:

    PYTHONPATH=src python -m repro.launch.train --mode vq \
        --executor mesh --scheme delta --workers 8 --tau 10 \
        [--network geometric --p-delay 0.5]

Hierarchical VQ — the paper's two-tier platform (cheap intra-host, slow
inter-host): ``--hosts 2`` splits the 8 workers into 2 host groups; tier-0
merges ride the dense ``--transport`` inside each group, tier-1 crosses
groups via ``--tier1-transport`` (sparse top-k by default) with per-tier
measured wire bytes:

    PYTHONPATH=src python -m repro.launch.train --mode vq --executor mesh \
        --workers 8 --hosts 2 [--tier1-transport sparse --tier1-frac 0.03]

Elastic VQ — the mesh run grows/shrinks its worker set mid-stream (a
resharding event per ``--resize`` entry, not a restart); with ``--ckpt-dir``
each resize checkpoints the shared prototypes, and ``--resume`` continues
from the latest resize point:

    PYTHONPATH=src python -m repro.launch.train --mode vq --executor mesh \
        --workers 8 --resize 20:4,40:8 [--ckpt-dir /tmp/ck] [--resume]

Adaptive communication — sync only when the codebooks have drifted, and
ship less when you do: ``--merge dynamic`` triggers the reducing phase on
measured divergence (``--divergence-thresh``, force-synced every
``--max-stale`` windows), ``--wire-quant int8`` quantizes the merge deltas
on the wire with error feedback, and ``--tier1-frac auto`` sizes the
sparse inter-host tier from measured bandwidth:

    PYTHONPATH=src python -m repro.launch.train --mode vq --executor mesh \
        --workers 8 --scheme delta --merge dynamic --divergence-thresh 5 \
        --wire-quant int8

Chaos VQ — seeded fault injection over any of the above: ``--chaos
"7:kill=2,slow=1,part=1"`` draws a deterministic kill/straggler/partition
schedule from seed 7, turns each death into an unscheduled elastic resize,
and rides the slow/partitioned workers through the straggler-tolerant
quorum merge (their deltas fold in late, damped by the stale-window rule):

    PYTHONPATH=src python -m repro.launch.train --mode vq --executor mesh \
        --workers 8 --scheme delta --chaos 7:kill=2,slow=1,part=1 \
        [--quorum-frac 0.6]

Runs on whatever devices exist (CPU smoke through full meshes): builds the
mesh, shards state via the same rules the dry-run proves out, streams the
deterministic synthetic pipeline, checkpoints asynchronously, and restarts
from the latest step when ``--resume`` is given (fault-tolerance path).
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import compile_cache
from repro.checkpoint.checkpointing import Checkpointer
from repro.configs import registry
from repro.data.pipeline import DataConfig, lm_batch
from repro.distributed import sharding
from repro.launch.mesh import make_host_mesh
from repro.models import common as model_common
from repro.optim import optimizers
from repro.training import steps as steps_lib


def run_vq(args) -> int:
    """The paper's schemes behind the engine's Executor API."""
    from repro import comm
    from repro.comm.sweep import acceptance_sparse_frac
    from repro.data import synthetic
    from repro.engine import get_executor, get_network
    from repro.obs import ExitFlush, MetricsRegistry, Profiler, Tracer
    from repro.topology import Topology

    # --trace records spans + counters for Perfetto; --metrics dumps the
    # registry as JSONL.  Either flag turns full instrumentation on (the
    # summary table needs the registry, the registry feeds on the tracer's
    # code paths), so one run can produce both artifacts.
    tracer = Tracer() if (args.trace or args.metrics) else None
    metrics = MetricsRegistry() if (args.trace or args.metrics) else None
    if args.profile and args.executor != "mesh":
        # attribution needs the compiled mesh program's HLO — sim replays
        # oracles, threads run eager python; neither has a program to parse
        print(f"error: --profile parses the compiled mesh program; got "
              f"--executor {args.executor}")
        return 2
    profiler = Profiler(metrics=metrics) if args.profile else None

    key = jax.random.PRNGKey(args.seed)
    kd, kw, ka = jax.random.split(key, 3)
    data = synthetic.replicate_stream(kd, args.workers, n=args.points,
                                      d=args.dim)
    eval_data = data[:, : min(1000, args.points)]
    w0 = synthetic.kmeanspp_init(kw, data.reshape(-1, args.dim), args.kappa)

    net_kw = {}
    if args.network == "fixed":
        net_kw["latency_ticks"] = args.latency
    elif args.network == "geometric":
        net_kw["p_delay"] = args.p_delay
    network = get_network(args.network, **net_kw)
    if (args.transport != "xla" or args.hosts > 1) and args.executor != "mesh":
        # sim replays oracles on one device and threads move blobs in
        # process: neither has a collective for a transport to reroute
        print(f"error: --transport {args.transport} / --hosts {args.hosts} "
              f"needs --executor mesh (the sim/thread backends issue no "
              f"collectives)")
        return 2
    transport = comm.get_transport(
        args.transport,
        **({"frac": args.compress_frac} if args.transport == "sparse"
           else {}))
    tier1_auto = args.tier1_frac == "auto"
    topology = None
    if args.hosts > 1:
        # hierarchical platform: the flat transport becomes tier 0 (dense
        # intra-host), tier 1 crosses the host groups — sparse by default,
        # at the k/kappa = 0.25 acceptance point unless --tier1-frac says
        # otherwise (the paper's slow-DCN regime).  'auto' also starts at
        # the acceptance point; the bandwidth controller takes over from
        # there.
        if args.tier1_frac is None or tier1_auto:
            tier1_frac = acceptance_sparse_frac(args.kappa, args.dim)
        else:
            try:
                tier1_frac = float(args.tier1_frac)
            except ValueError:
                print(f"error: --tier1-frac must be a float or 'auto', "
                      f"got {args.tier1_frac!r}")
                return 2
        try:
            # build the tier-1 transport FIRST: a bad --tier1-frac should
            # report as a frac error even on a box with too few devices
            # for the worker mesh
            tier1 = (comm.get_transport("sparse", frac=tier1_frac)
                     if args.tier1_transport == "sparse"
                     else args.tier1_transport)
            topology = Topology.from_spec(args.workers, hosts=args.hosts)
            transport = comm.HierarchicalTransport(
                tier0=transport, tier1=tier1,
                host_axis=topology.host_axis,
                worker_axis=topology.worker_axis)
        except ValueError as e:  # bad tier-1 frac / hosts split
            print(f"error: {e}")
            return 2
    if args.wire_quant != "off":
        # quantized wire format decorates the WHOLE transport stack (flat
        # or hierarchical): deltas cross every link at the narrow width,
        # the error-feedback residual re-injects the rounding error
        if args.executor != "mesh":
            print(f"error: --wire-quant quantizes the mesh transport's "
                  f"collectives; got --executor {args.executor}")
            return 2
        transport = comm.get_transport("quant", inner=transport,
                                       mode=args.wire_quant)
    tier1_controller = None
    if tier1_auto:
        if args.executor != "mesh":
            print(f"error: --tier1-frac auto adapts the mesh transport's "
                  f"sparse tier; got --executor {args.executor}")
            return 2
        if args.hosts <= 1 and args.transport != "sparse":
            print("error: --tier1-frac auto needs a sparse tier to adapt "
                  "(--hosts > 1 with a sparse --tier1-transport, or a flat "
                  "--transport sparse)")
            return 2
        if args.resize or args.chaos:
            print("error: --tier1-frac auto is a plain-mesh feature; it "
                  "does not compose with --resize/--chaos")
            return 2
        from repro.engine import Tier1BudgetController
        tier1_controller = Tier1BudgetController(
            network, budget_ticks=args.tier1_budget_ticks)
    chaos = None
    if args.chaos:
        # seeded fault injection: parse the schedule against the run's
        # window count, wrap the network model so the executors see the
        # faults, and (below) go elastic if any worker dies
        from repro.engine import ChaosNetwork, ChaosSchedule
        if args.executor != "mesh":
            print(f"error: --chaos injects faults into the mesh executors; "
                  f"got --executor {args.executor}")
            return 2
        try:
            chaos = ChaosSchedule.from_spec(
                args.chaos, windows=args.points // args.tau, m=args.workers,
                hosts=args.hosts if args.hosts > 1 else 2)
        except ValueError as e:
            print(f"error: {e}")
            return 2
        network = ChaosNetwork(network, chaos, topology=topology)
        print(f"chaos: {chaos.describe()}")
    if args.resume and not args.resize:
        # only the elastic path has VQ resume state; a plain executor would
        # silently restart from scratch, which is not a resume
        print("error: --resume in VQ mode needs --resize (elastic runs "
              "checkpoint at resize events; plain runs have no VQ "
              "checkpoint to restore)")
        return 2
    # merge strategy: --chaos/--quorum imply the straggler-tolerant quorum
    # merge (an injected fault must not deadlock the barrier); --merge
    # dynamic opts into divergence-triggered syncs.  Both fold eq.-8
    # displacements, so both ride the delta scheme only.
    merge = args.merge
    if args.chaos or args.quorum:
        if merge == "dynamic":
            print("error: --merge dynamic conflicts with --chaos/--quorum "
                  "(faults ride the quorum merge's late matrix; the "
                  "dynamic merge has no lateness channel)")
            return 2
        merge = "quorum"
    if merge is not None and args.scheme != "delta":
        print(f"error: the {merge} merge folds eq.-8 displacements, so it "
              f"needs --scheme delta; got {args.scheme!r}")
        return 2
    if merge == "dynamic":
        if args.executor != "mesh":
            print(f"error: --merge dynamic runs the divergence probe "
                  f"inside the compiled mesh program; got --executor "
                  f"{args.executor}")
            return 2
        if args.resize:
            print("error: --merge dynamic does not compose with --resize "
                  "(the elastic path reshards quorum/plain merge state "
                  "only)")
            return 2
    ckpt = None
    needs_elastic = bool(args.resize) or (chaos is not None
                                          and chaos.kill_events)
    if needs_elastic:
        if args.executor != "mesh":
            print(f"error: --resize is a mesh-executor feature (elastic "
                  f"resharding of the device mesh); got --executor "
                  f"{args.executor}")
            return 2
        if args.resume and not args.ckpt_dir:
            print("error: --resume needs --ckpt-dir (the elastic resume "
                  "restores the latest resize checkpoint)")
            return 2
        if args.wire_quant != "off":
            print("error: --wire-quant does not compose with elastic "
                  "resizes (the error-feedback residual is per-worker "
                  "state the resharder does not carry across a resize)")
            return 2
        ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
        ex_name = "elastic"
        ex_kw = {"schedule": args.resize if args.resize else [],
                 "network": network,
                 "transport": transport, "topology": topology,
                 "checkpointer": ckpt, "resume": args.resume,
                 "chaos": chaos, "merge": merge,
                 "quorum_frac": args.quorum_frac}
    elif args.executor == "thread":
        # real threads have no tick clock: tick-based NetworkModels don't
        # apply, and silently dropping them would mislabel the run
        if args.network != "instant":
            print(f"error: --network {args.network} is tick-based; the "
                  f"thread backend models communication in seconds — use "
                  f"--comm-delay-s instead")
            return 2
        ex_name = args.executor
        ex_kw = {"duration_s": args.duration_s,
                 "comm_delay_s": args.comm_delay_s}
    else:
        ex_name = args.executor
        ex_kw = {"network": network}
        if args.executor == "mesh":
            ex_kw["transport"] = transport
            ex_kw["topology"] = topology
            if merge == "quorum":
                ex_kw["merge"] = merge
                ex_kw["quorum_frac"] = args.quorum_frac
            elif merge == "dynamic":
                ex_kw["merge"] = merge
                ex_kw["divergence_thresh"] = args.divergence_thresh
                ex_kw["max_stale"] = args.max_stale
            if tier1_controller is not None:
                ex_kw["tier1_controller"] = tier1_controller
    ex_kw["tracer"] = tracer
    ex_kw["metrics"] = metrics
    if profiler is not None:
        ex_kw["profiler"] = profiler
    try:
        executor = get_executor(ex_name, **ex_kw)
    except ValueError as e:  # bad resize spec
        print(f"error: {e}")
        return 2
    # arm the crash-path flush BEFORE the run: a chaos kill or Ctrl-C must
    # still leave the trace/metrics artifacts on disk (the happy path
    # flushes the same object, so they are written exactly once)
    flusher = None
    if args.trace or args.metrics:
        flusher = ExitFlush(
            tracer=tracer if args.trace else None,
            trace_path=args.trace or None,
            metrics=metrics if args.metrics else None,
            metrics_path=args.metrics or None,
            run=f"train-vq-{args.scheme}-{executor.name}",
            catch_sigterm=True)

    print(f"executor={executor.name} scheme={args.scheme} "
          f"M={args.workers} tau={args.tau} network={args.network} "
          f"transport={transport.name} devices={len(jax.devices())}"
          + (f" topology={topology.describe()}"
             f" tier1={args.tier1_transport}" if topology is not None
             else "")
          + (f" resize={args.resize}" if args.resize else ""))
    t0 = time.perf_counter()
    try:
        res = executor.run(args.scheme, w0, data, eval_data, tau=args.tau,
                           eps0=args.eps0, key=ka)
    except ValueError as e:  # bad scheme/mesh/shape/resume combination
        print(f"error: {e}")
        return 2
    jax.block_until_ready(res.w_shared)
    wall = time.perf_counter() - t0
    curve = np.asarray(res.distortion)
    ticks = np.asarray(res.wall_ticks)
    idx = np.unique(np.linspace(0, len(curve) - 1, 10).astype(int))
    unit = "s" if executor.name == "thread" else "ticks"
    for i in idx:
        print(f"  {unit} {float(ticks[i]):>8.1f}  C = {curve[i]:.5f}")
    for ev in getattr(executor, "resize_events", []):
        ck = (f" ckpt@{ev.checkpoint_step}"
              if ev.checkpoint_step is not None else "")
        print(f"  resize @window {ev.window}: M {ev.old_m} -> {ev.new_m} "
              f"(late points merged: {ev.late_points}, "
              f"{ev.wall_s * 1e3:.1f} ms{ck})")
    pts = args.workers * args.points
    print(f"done: C(final)={curve[-1]:.5f} in {wall:.2f}s wall "
          f"({wall / pts * 1e6:.2f} us/point over {pts} points)")
    last_comm = getattr(executor, "last_comm", None)
    if last_comm:
        merge_b = last_comm["by_tag"].get("merge", {"wire_bytes": 0,
                                                    "logical_bytes": 0})
        print(f"comm[{transport.name}]: merge wire "
              f"{merge_b['wire_bytes']:,} B / logical "
              f"{merge_b['logical_bytes']:,} B per worker "
              f"({last_comm['calls']} collective calls, measured)")
        for tier, t in sorted(merge_b.get("by_tier", {}).items()):
            label = "intra-host" if tier == 0 else "inter-host"
            print(f"  tier {tier} ({label}): wire {t['wire_bytes']:,} B "
                  f"/ logical {t['logical_bytes']:,} B per worker")
    if profiler is not None:
        print("profile (roofline attribution):")
        print(profiler.summary_table())
        profiler.export_json(args.profile)
        print(f"profile: {len(profiler.attributions)} run(s) -> "
              f"{args.profile} (render: python -m repro.obs.report "
              f"--profile {args.profile})")
    if metrics is not None:
        print("metrics:")
        print(metrics.summary_table())
    if flusher is not None:
        flusher.flush()
        if args.trace:
            print(f"trace: {len(tracer.spans())} spans -> {args.trace} "
                  f"(load at https://ui.perfetto.dev)")
        if args.metrics:
            print(f"metrics: appended -> {args.metrics}")
    if ckpt is not None:
        ckpt.wait()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("lm", "vq"), default="lm")
    ap.add_argument("--arch", default="granite_8b",
                    choices=registry.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--data-axis", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    # VQ-mode options (--mode vq): engine backend + paper hyperparameters
    ap.add_argument("--executor", choices=("sim", "mesh", "thread"),
                    default="sim")
    ap.add_argument("--scheme",
                    choices=("average", "delta", "async_delta"),
                    default="delta")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--points", type=int, default=2000,
                    help="data points per worker")
    ap.add_argument("--dim", type=int, default=8)
    ap.add_argument("--kappa", type=int, default=16)
    ap.add_argument("--tau", type=int, default=10)
    ap.add_argument("--eps0", type=float, default=0.5)
    ap.add_argument("--network",
                    choices=("instant", "fixed", "geometric"),
                    default="instant")
    ap.add_argument("--transport", choices=("xla", "ring", "sparse"),
                    default="xla",
                    help="merge transport (mesh/elastic executors): dense "
                         "XLA collectives, Pallas ring all-reduce (TPU; "
                         "XLA fallback on CPU), or top-k/error-feedback "
                         "sparse")
    ap.add_argument("--compress-frac", type=float, default=0.01,
                    help="sparse transport: fraction of entries each "
                         "worker ships per merge")
    ap.add_argument("--hosts", type=int, default=1,
                    help="hierarchical topology: split the M workers into "
                         "this many host groups (M must divide evenly); "
                         "merges then run dense intra-host (tier 0, the "
                         "--transport choice) and --tier1-transport "
                         "inter-host (tier 1), with per-tier wire "
                         "accounting")
    ap.add_argument("--tier1-transport", choices=("xla", "ring", "sparse"),
                    default="sparse",
                    help="--hosts > 1: the inter-host (DCN) tier's "
                         "transport; sparse (top-k + error feedback) is "
                         "the paper's slow-link answer, xla the dense "
                         "bit-exact baseline")
    ap.add_argument("--tier1-frac", default=None,
                    help="sparse tier 1: keep-fraction of entries per "
                         "inter-host merge (default: the k/kappa = 0.25 "
                         "acceptance point), or 'auto' to size it from "
                         "measured bandwidth — a host-side controller "
                         "halves/doubles the fraction so the inter-host "
                         "transfer stays on --tier1-budget-ticks wall "
                         "ticks per window")
    ap.add_argument("--tier1-budget-ticks", type=int, default=2,
                    help="--tier1-frac auto: target wall ticks per window "
                         "for the tier-1 (DCN) transfer")
    ap.add_argument("--latency", type=int, default=1)
    ap.add_argument("--p-delay", type=float, default=0.5)
    ap.add_argument("--resize", default="",
                    help="elastic resize schedule 'WINDOW:M,...' (e.g. "
                         "'20:4,40:8'); mesh executor only")
    ap.add_argument("--chaos", default="",
                    metavar="SEED:SCHEDULE",
                    help="seeded fault injection, e.g. '7:kill=2,slow=1,"
                         "part=1' — draw that many worker deaths, "
                         "stragglers, and host-group partitions from SEED; "
                         "kills become unscheduled elastic resizes, "
                         "slow/partition ride the quorum merge's late "
                         "matrix; mesh executor + --scheme delta only")
    ap.add_argument("--quorum", action="store_true",
                    help="use the straggler-tolerant quorum merge even "
                         "without --chaos (delta scheme only)")
    ap.add_argument("--quorum-frac", type=float, default=0.6,
                    help="quorum merge: fraction of workers whose deltas "
                         "must arrive for the merge to apply (late deltas "
                         "fold in damped by the stale-window rule)")
    ap.add_argument("--merge", choices=("quorum", "dynamic"), default=None,
                    help="merge strategy override (delta scheme, mesh "
                         "executor): 'quorum' = the straggler-tolerant "
                         "merge (same as --quorum), 'dynamic' = "
                         "divergence-triggered merges — workers sync only "
                         "on windows where the measured codebook drift "
                         "crosses --divergence-thresh (Kamp-style dynamic "
                         "averaging), capped by --max-stale")
    ap.add_argument("--divergence-thresh", type=float, default=0.0,
                    help="--merge dynamic: global squared-drift threshold "
                         "that fires a sync; 0.0 syncs every window "
                         "(bitwise-identical to the plain delta merge)")
    ap.add_argument("--max-stale", type=int, default=8,
                    help="--merge dynamic: force a sync after this many "
                         "consecutive skipped windows (bounds the eq.-8 "
                         "staleness damping)")
    ap.add_argument("--wire-quant", choices=("off", "bf16", "int8"),
                    default="off",
                    help="quantize merge deltas on the wire (mesh "
                         "executor): bf16 halves, int8 quarters the merge "
                         "wire bytes, both with error-feedback residual so "
                         "the quantization error re-enters the next merge")
    ap.add_argument("--duration-s", type=float, default=2.0,
                    help="thread backend: wall seconds to run")
    ap.add_argument("--comm-delay-s", type=float, default=0.0,
                    help="thread backend: per-round comm latency (seconds)")
    ap.add_argument("--trace", default="", metavar="OUT.json",
                    help="write a Chrome trace-event file (Perfetto): "
                         "per-worker window/compute spans, per-tier merge "
                         "spans, distortion + codebook-divergence counters")
    ap.add_argument("--metrics", default="", metavar="OUT.jsonl",
                    help="append the metrics registry (counters/gauges/"
                         "histograms) as JSONL, one object per metric")
    ap.add_argument("--profile", default="", metavar="PROF.json",
                    help="roofline-attribute the run (mesh executor only): "
                         "decompose measured per-window wall into analytic "
                         "compute/HBM terms, the compiled program's HLO "
                         "collective bytes, and the host residual; prints "
                         "the attribution table and writes the Profiler "
                         "export (render with repro.obs.report --profile)")
    ap.add_argument("--autotune", choices=("off", "cache"),
                    default="cache",
                    help="Pallas tile selection: 'off' pins the legacy "
                         "(128, 128) tiles, 'cache' picks per shape from "
                         "the roofline model (memoized)")
    ap.add_argument("--autotune-cache", default="", metavar="TILES.json",
                    help="persist tuned tile configs to this JSON file "
                         "(also read at startup; keyed by shape AND device "
                         "kind, so a cache never leaks across accelerators)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.enable()

    from repro.kernels import autotune
    autotune.set_mode(args.autotune)
    if args.autotune_cache:
        autotune.set_cache_path(args.autotune_cache)

    if args.mode == "vq":
        return run_vq(args)

    cfg = (registry.get_smoke_config(args.arch) if args.smoke
           else registry.get_config(args.arch))
    mesh = make_host_mesh(data=args.data_axis)
    model_common.set_run_options(mesh=mesh)
    print(f"arch={cfg.name} devices={len(jax.devices())} "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq_len,
                      global_batch=args.batch)
    opt = optimizers.adamw(optimizers.cosine_schedule(
        args.lr, warmup=20, total=args.steps))
    pspecs = sharding.param_specs(cfg, mesh, use_fsdp=False)
    step_fn = steps_lib.make_train_step(cfg, opt)

    state = steps_lib.init_train_state(cfg, opt, jax.random.PRNGKey(0))
    state_specs = {
        "params": pspecs,
        "opt_state": sharding.opt_specs_like(pspecs, state["opt_state"]),
        "step": jax.sharding.PartitionSpec(),
    }
    state = jax.device_put(state, sharding.named(mesh, state_specs))
    jit_step = jax.jit(step_fn, donate_argnums=(0,))

    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if ckpt and args.resume:
        latest = ckpt.latest_step()
        if latest is not None:
            state = ckpt.restore(latest, state,
                                 shardings=sharding.named(mesh, state_specs))
            start = latest
            print(f"resumed from step {start}")

    t0 = time.perf_counter()
    with mesh:
        for i in range(start, args.steps):
            batch = lm_batch(dcfg, i)  # step-indexed: restart-deterministic
            state, metrics = jit_step(state, batch)
            if (i + 1) % args.log_every == 0:
                loss = float(metrics["loss"])
                tps = ((i + 1 - start) * args.batch * args.seq_len
                       / (time.perf_counter() - t0))
                print(f"step {i + 1:5d}  loss {loss:.4f}  "
                      f"gnorm {float(metrics['grad_norm']):.2f}  "
                      f"tok/s {tps:,.0f}")
            if ckpt and (i + 1) % args.ckpt_every == 0:
                ckpt.save_async(i + 1, state)
    if ckpt:
        ckpt.wait()
    print(f"done: {args.steps - start} steps in {time.perf_counter() - t0:.1f}s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
