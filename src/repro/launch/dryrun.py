"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: ShapeDtypeStruct
inputs only (no allocation), ``jit(...).lower(...).compile()`` on 512
placeholder host devices, and extracts memory / cost / collective stats for
the roofline analysis.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch olmoe_1b_7b \
        --shape train_4k [--multi-pod] [--merge delta --tau 10]
    PYTHONPATH=src python -m repro.launch.dryrun --all   # full 40-cell sweep
    PYTHONPATH=src python -m repro.launch.dryrun --comm  # scheme x transport
        # wire bytes: runs the engine suite through every repro.comm
        # transport and reports the MEASURED per-worker merge traffic from
        # the CommRecord stream (not a model)
"""

# MUST run before any other import: jax locks the device count on first init.
import os  # noqa: E402
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import registry  # noqa: E402
from repro.distributed import hlo_analysis, roofline, sharding  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models.api import get_api  # noqa: E402
from repro.optim import optimizers  # noqa: E402
from repro.training import steps as steps_lib  # noqa: E402


# ---------------------------------------------------------------------------
# cell construction
# ---------------------------------------------------------------------------

def build_cell(arch_id: str, shape_name: str, *, multi_pod: bool,
               merge: str = "none", tau: int = 10, seq_parallel: bool = True,
               quantized: bool = False):
    """Returns (lower_fn, mesh) — lower_fn() does the lower+compile."""
    from repro.models import common as model_common

    cfg = registry.get_config(arch_id)
    cell = next(s for s in registry.SHAPES if s.name == shape_name)
    ok, why = registry.cell_applicable(cfg, cell)
    if not ok:
        return None, why

    mesh = make_production_mesh(multi_pod=multi_pod)
    use_window = merge != "none" and multi_pod
    # activation sharding constraints (SP) target the mesh directly; inside
    # the shard_map window step constraints would name manual axes, so SP is
    # disabled there (the window lowering measures collectives, not memory).
    model_common.set_run_options(
        mesh=None if use_window else mesh,
        seq_parallel=seq_parallel)
    # FSDP is a TRAINING memory tool (opt-state sharding).  Serving reads
    # every param each step, so 'data'-sharded params would all-gather per
    # token: inference cells are TP-only (EXPERIMENTS.md §Perf it.6).
    use_fsdp = registry.uses_fsdp(arch_id) and cell.kind == "train"
    pspecs = sharding.param_specs(cfg, mesh, use_fsdp=use_fsdp)
    api = get_api(cfg)

    if cell.kind == "train":
        opt = optimizers.adamw(optimizers.cosine_schedule(3e-4))
        state_shapes = jax.eval_shape(
            lambda: steps_lib.init_train_state(
                cfg, opt, jax.random.PRNGKey(0)))
        opt_specs = sharding.opt_specs_like(pspecs, state_shapes["opt_state"])
        state_specs = {"params": pspecs, "opt_state": opt_specs, "step": P()}

        if merge != "none" and multi_pod:
            strategy = steps_lib.Merge(merge)
            step = steps_lib.make_window_step(
                cfg, opt, mesh, tau=tau, merge=strategy, merge_axis="pod")
            state_shapes = jax.eval_shape(
                lambda: steps_lib.init_window_state(
                    cfg, opt, jax.random.PRNGKey(0), strategy))
            state_specs = dict(state_specs)
            for extra in ("delta_prev", "residual"):
                if extra in state_shapes:
                    state_specs[extra] = pspecs
            batch = registry.input_specs(cfg, cell, tau=tau)
            bspecs = jax.tree.map(
                lambda s: P(None, *sharding.batch_specs(
                    cfg, mesh, {"x": jax.ShapeDtypeStruct(
                        s.shape[1:], s.dtype)})["x"]), batch)
        else:
            step = steps_lib.make_train_step(cfg, opt)
            batch = registry.input_specs(cfg, cell)
            bspecs = sharding.batch_specs(cfg, mesh, batch)

        in_shardings = (sharding.named(mesh, state_specs),
                        sharding.named(mesh, bspecs))
        out_shardings = (sharding.named(mesh, state_specs), None)

        def lower():
            with mesh:
                return jax.jit(
                    step, in_shardings=in_shardings,
                    out_shardings=out_shardings, donate_argnums=(0,),
                ).lower(state_shapes, batch)

        return lower, ""

    if cell.kind == "prefill":
        # real prefill: forward over the prompt AND the decode-cache fill
        step = steps_lib.make_prefill_step(cfg, max_len=cell.seq_len)
        batch = registry.input_specs(cfg, cell)
        bspecs = sharding.batch_specs(cfg, mesh, batch)
        param_shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
        cache_cell = registry.ShapeCell(
            cell.name, "decode", cell.seq_len, cell.global_batch)
        cspecs = sharding.cache_specs(
            cfg, mesh, registry.cache_shapes(cfg, cache_cell))
        in_shardings = (sharding.named(mesh, pspecs),
                        sharding.named(mesh, bspecs))
        out_shardings = (None, sharding.named(mesh, cspecs))

        def lower():
            with mesh:
                return jax.jit(
                    step, in_shardings=in_shardings,
                    out_shardings=out_shardings,
                ).lower(param_shapes, batch)

        return lower, ""

    # decode
    step = steps_lib.make_serve_step(cfg, quantized=quantized)
    batch = registry.input_specs(cfg, cell)
    cache = registry.cache_shapes(cfg, cell)
    cspecs = sharding.cache_specs(cfg, mesh, cache)
    bspecs = sharding.batch_specs(cfg, mesh, batch)
    param_shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    if quantized:
        from repro.models import quantization
        param_shapes = jax.eval_shape(
            lambda p: quantization.quantize_tree(p), param_shapes)
        flat_q, td = jax.tree.flatten(
            param_shapes,
            is_leaf=lambda x: isinstance(x, quantization.QuantizedLeaf))
        flat_s = jax.tree.leaves(pspecs)
        pspecs = jax.tree.unflatten(td, [
            quantization.QuantizedLeaf(
                q=s, scale=P(*([None] * q.scale.ndim)), dtype=q.dtype)
            if isinstance(q, quantization.QuantizedLeaf) else s
            for q, s in zip(flat_q, flat_s)])
    in_shardings = (sharding.named(mesh, pspecs),
                    sharding.named(mesh, cspecs),
                    sharding.named(mesh, bspecs)["tokens"])
    out_shardings = (None, sharding.named(mesh, cspecs))

    def lower():
        with mesh:
            return jax.jit(
                step, in_shardings=in_shardings,
                out_shardings=out_shardings, donate_argnums=(1,),
            ).lower(param_shapes, cache, batch["tokens"])

    return lower, ""


def build_vq_cell(shape_name: str, *, multi_pod: bool, tau: int = 10):
    """The PAPER'S OWN workload at pod scale: distributed VQ over a sharded
    dataset.  Shapes: vq_stream (paper-faithful S2 window: per-worker
    sequential scans + delta psum) and vq_batch (MXU-optimal fused
    minibatch displacement).  kappa=16384, d=512 — production codebook
    scale (RQ-VAE-size); one worker per DP device."""
    from repro.core import dvq

    mesh = make_production_mesh(multi_pod=multi_pod)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = int(np.prod([sizes[a] for a in ("pod", "data") if a in sizes]))
    kappa, d = 16384, 512
    if shape_name == "vq_stream":
        step = dvq.make_window_vq_step(tau=tau)
        z = jax.ShapeDtypeStruct((dp, tau, d), jnp.float32)
        z_spec = P(tuple(a for a in ("pod", "data") if a in sizes),
                   None, None)
    else:  # vq_batch
        step = dvq.make_minibatch_vq_step(use_kernel=False)
        batch = 1 << 20  # 1M points per step
        z = jax.ShapeDtypeStruct((batch, d), jnp.float32)
        _, z_sh = dvq.vq_shardings(mesh, kappa=kappa, d=d, batch=batch)
        z_spec = z_sh.spec
    w_sh, _ = dvq.vq_shardings(mesh, kappa=kappa, d=d, batch=1)
    w = jax.ShapeDtypeStruct((kappa, d), jnp.float32)
    t = jax.ShapeDtypeStruct((), jnp.int32)
    in_shardings = (w_sh, NamedSharding(mesh, P()),
                    NamedSharding(mesh, z_spec))

    def lower():
        with mesh:
            return jax.jit(step, in_shardings=in_shardings,
                           donate_argnums=(0,)).lower(w, t, z)

    return lower, ""


def run_cell(arch_id: str, shape_name: str, *, multi_pod: bool,
             merge: str = "none", tau: int = 10, verbose: bool = True,
             quantized: bool = False) -> dict:
    t0 = time.perf_counter()
    rec: dict = {"arch": arch_id, "shape": shape_name,
                 "mesh": "2x16x16" if multi_pod else "16x16",
                 "merge": merge}
    if quantized:
        rec["quantized"] = True
    if arch_id == "paper_vq":
        lower_fn, why = build_vq_cell(shape_name, multi_pod=multi_pod,
                                      tau=tau)
    else:
        lower_fn, why = build_cell(arch_id, shape_name, multi_pod=multi_pod,
                                   merge=merge, tau=tau,
                                   quantized=quantized)
    if lower_fn is None:
        rec["status"] = "skipped"
        rec["reason"] = why
        if verbose:
            print(f"SKIP {arch_id} x {shape_name}: {why}")
        return rec
    try:
        lowered = lower_fn()
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = hlo_analysis.analyze_collectives(hlo)
        if arch_id == "paper_vq":
            n_dev = 512 if multi_pod else 256
            kappa, d = 16384, 512
            if shape_name == "vq_batch":
                flops = 4.0 * (1 << 20) * kappa * d / n_dev
                hbm = ((1 << 20) * d * 4 / n_dev + kappa * d * 4 * 3
                       / (16 if kappa % 16 == 0 else 1))
            else:
                dp = n_dev // 16
                flops = 4.0 * dp * tau * kappa * d / n_dev
                hbm = kappa * d * 4 * 3
            terms = {
                "t_compute": flops / roofline.MODEL_PEAKS.flops,
                "t_memory": hbm / roofline.MODEL_PEAKS.hbm_bw,
                "t_collective": (coll["total_bytes"]
                                 / roofline.MODEL_PEAKS.ici_bw),
            }
            terms["dominant"] = max(
                ("compute", "memory", "collective"),
                key=lambda k: terms[f"t_{k}"])
            rec.update({
                "status": "ok",
                "compile_s": round(time.perf_counter() - t0, 1),
                "collectives": coll, "roofline": terms,
                "memory": {"peak_bytes": getattr(
                    mem, "peak_memory_in_bytes", 0)},
            })
            if verbose:
                print(f"OK   paper_vq x {shape_name} [{rec['mesh']}]"
                      f" compile={rec['compile_s']}s"
                      f" coll={coll['total_bytes']:.3e}B"
                      f" t=({terms['t_compute']:.6f},"
                      f"{terms['t_memory']:.6f},"
                      f"{terms['t_collective']:.6f})s"
                      f" dom={terms['dominant']}")
            return rec
        cfg = registry.get_config(arch_id)
        cell = next(s for s in registry.SHAPES if s.name == shape_name)
        # window steps lower tau local steps in one program: normalize the
        # collective term to per-step so cells are comparable
        per_step_div = tau if (merge != "none" and multi_pod) else 1
        terms = roofline.roofline_terms(
            cfg, cell, roofline.mesh_shape(multi_pod),
            coll["total_bytes"] / per_step_div)
        rec["per_step_divisor"] = per_step_div
        rec["t_collective_tpu_adjusted"] = (
            coll["tpu_adjusted_bytes"] / per_step_div
            / roofline.MODEL_PEAKS.ici_bw)
        rec.update({
            "status": "ok",
            "compile_s": round(time.perf_counter() - t0, 1),
            "cost_flops_bodyonce": float(cost.get("flops", 0.0)),
            "cost_bytes_bodyonce": float(cost.get("bytes accessed", 0.0)),
            "collectives": coll,
            "roofline": terms,
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                "peak_bytes": getattr(mem, "peak_memory_in_bytes", 0),
            },
        })
        if verbose:
            gb = rec["memory"]["peak_bytes"] / 2**30
            print(f"OK   {arch_id} x {shape_name} [{rec['mesh']},"
                  f" merge={merge}] compile={rec['compile_s']}s"
                  f" coll={coll['total_bytes']:.3e}B"
                  f" dom={terms['dominant']}"
                  f" t=({terms['t_compute']:.4f},{terms['t_memory']:.4f},"
                  f"{terms['t_collective']:.4f})s"
                  f" mfu<={terms['mfu_bound']:.2f}"
                  f" peak={gb:.2f}GiB/dev")
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"[:2000]
        if verbose:
            print(f"FAIL {arch_id} x {shape_name} [{rec['mesh']}]: "
                  f"{rec['error'][:300]}")
    return rec


# ---------------------------------------------------------------------------
# scheme x transport comm suite (measured wire bytes)
# ---------------------------------------------------------------------------

def run_comm_suite(*, sparse_frac: float | None = None,
                   verbose: bool = True) -> list[dict]:
    """Run the engine suite through every transport and report the wire
    bytes the ``CommRecord`` stream MEASURED (trace-exact shapes, replayed
    per execution) — not the roofline model's estimate.

    ``sparse_frac`` defaults to k/kappa = 0.25 (k = kappa/4 entries kept of
    the kappa*d displacement), the ISSUE-4 acceptance point where the
    sparse wire must come in >= 4x under dense.  The sweep itself is the
    shared ``repro.comm.sweep`` (one definition for this report and the
    ``--suite comm`` CI gate).

    The flat scheme x transport table is followed by the hierarchical
    cells (2-host topology, per-tier intra/inter columns): sparse tier 1
    must cut the INTER-host wire >= 4x under the dense tier 1 at the same
    acceptance point — the ISSUE-5 bar, exit-coded alongside the flat one.
    """
    from repro.comm import sweep

    cells = sweep.run_comm_cells(sparse_frac=sparse_frac, repeats=0)
    dense_wire = {c["scheme"]: c["merge_wire_bytes"] for c in cells
                  if c["transport"] == "xla"}
    records: list[dict] = []
    for c in cells:
        rec = {"arch": "comm", "shape": c["scheme"],
               "mesh": f"{c['m']}x1", "merge": c["scheme"],
               "transport": c["transport"], "status": "ok", **{
                   k: c[k] for k in (
                       "m", "n", "d", "kappa", "tau", "compile_s",
                       "merge_wire_bytes", "merge_logical_bytes",
                       "collective_calls", "final_C")}}
        if c["transport"] == "sparse":
            rec["sparse_frac"] = c["sparse_frac"]
            rec["wire_reduction_vs_dense"] = (
                dense_wire.get(c["scheme"], 0) / c["merge_wire_bytes"]
                if c["merge_wire_bytes"] else float("inf"))
        records.append(rec)
        if verbose:
            extra = (f" reduction={rec['wire_reduction_vs_dense']:.2f}x"
                     if c["transport"] == "sparse" else "")
            print(f"COMM {c['scheme']:<12s} x {c['transport']:<6s} "
                  f"wire={c['merge_wire_bytes']:>10,}B "
                  f"logical={c['merge_logical_bytes']:>10,}B{extra}")

    hier = sweep.run_hier_cells(tier1_frac=sparse_frac, repeats=0)
    dense_inter = {c["scheme"]: c["tier1_wire_bytes"] for c in hier
                   if c["variant"] == "hier_dense"}
    for c in hier:
        if c["variant"] == "flat":
            continue
        rec = {"arch": "comm_hier", "shape": c["scheme"],
               "mesh": f"{c['hosts']}x{c['workers_per_host']}",
               "merge": c["scheme"], "transport": c["variant"],
               "status": "ok", **{k: c[k] for k in (
                   "m", "n", "d", "kappa", "tau", "compile_s", "hosts",
                   "workers_per_host", "merge_wire_bytes",
                   "tier0_wire_bytes", "tier1_wire_bytes", "final_C",
                   "bitmatch_flat")}}
        if c["variant"] == "hier_sparse":
            rec["tier1_frac"] = c["tier1_frac"]
            rec["inter_reduction_vs_dense"] = (
                dense_inter.get(c["scheme"], 0) / c["tier1_wire_bytes"]
                if c["tier1_wire_bytes"] else float("inf"))
        records.append(rec)
        if verbose:
            extra = (f" inter_reduction="
                     f"{rec['inter_reduction_vs_dense']:.2f}x"
                     if c["variant"] == "hier_sparse" else
                     f" bitmatch_flat={c['bitmatch_flat']}")
            print(f"HIER {c['scheme']:<12s} x {c['variant']:<12s} "
                  f"[{rec['mesh']}] intra={c['tier0_wire_bytes']:>9,}B "
                  f"inter={c['tier1_wire_bytes']:>9,}B{extra}")

    # adaptive cells: {fixed, dynamic} merge x {dense, bf16, int8} wire —
    # the dynamic merge must hold total (merge + probe) wire at or under
    # its fixed counterpart at every quant level, or the probe isn't
    # paying for itself
    adapt = sweep.run_adapt_cells(repeats=0)
    fixed_wire = {c["quant"]: c["total_wire_bytes"] for c in adapt
                  if c["merge"] == "fixed"}
    for c in adapt:
        rec = {"arch": "comm_adapt", "shape": "delta",
               "mesh": f"{c['m']}x1", "merge": c["merge"],
               "transport": c["quant"], "status": "ok", **{
                   k: c[k] for k in (
                       "m", "n", "d", "kappa", "tau", "quant", "thresh",
                       "compile_s", "merge_wire_bytes", "probe_wire_bytes",
                       "total_wire_bytes", "n_windows", "n_triggered",
                       "final_C")}}
        if c["merge"] == "dynamic":
            rec["wire_vs_fixed"] = (c["total_wire_bytes"]
                                    / max(fixed_wire[c["quant"]], 1))
        records.append(rec)
        if verbose:
            extra = (f" vs_fixed={rec['wire_vs_fixed']:.2f}x"
                     if c["merge"] == "dynamic" else "")
            print(f"ADPT {c['merge']:<8s} x {c['quant']:<6s} "
                  f"wire={c['total_wire_bytes']:>8,}B "
                  f"(merge {c['merge_wire_bytes']:,}B + probe "
                  f"{c['probe_wire_bytes']:,}B) "
                  f"trig={c['n_triggered']}/{c['n_windows']}{extra}")
    return records


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=registry.ARCH_IDS + ["paper_vq"])
    ap.add_argument("--shape",
                    choices=[s.name for s in registry.SHAPES]
                    + ["vq_batch", "vq_stream"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--merge", default="none",
                    choices=["none", "allreduce", "average", "delta",
                             "async_delta", "delta_sparse"])
    ap.add_argument("--tau", type=int, default=10)
    ap.add_argument("--quantized", action="store_true",
                    help="int8 weight-only decode (decode cells only)")
    ap.add_argument("--comm", action="store_true",
                    help="engine comm suite: measured wire bytes per "
                         "scheme x transport (8-worker mesh)")
    ap.add_argument("--sparse-frac", type=float, default=None,
                    help="--comm: sparse transport keep-fraction "
                         "(default: k/kappa = 0.25, the acceptance point)")
    ap.add_argument("--out", default="benchmarks/results/dryrun.json")
    args = ap.parse_args(argv)

    if args.comm:
        results = run_comm_suite(sparse_frac=args.sparse_frac)
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        existing = []
        if os.path.exists(args.out):
            with open(args.out) as f:
                existing = json.load(f)
        keyf = lambda r: (r["arch"], r["shape"], r["mesh"],  # noqa: E731
                          r.get("merge", "none"), r.get("quantized", False),
                          r.get("transport", "none"))
        merged = {keyf(r): r for r in existing}
        for r in results:
            merged[keyf(r)] = r
        with open(args.out, "w") as f:
            json.dump(list(merged.values()), f, indent=1)
        # compression applies to displacement merges; 'average' ships means,
        # which ride dense on every transport (see comm.sparse docstring)
        worst = min((r["wire_reduction_vs_dense"] for r in results
                     if r.get("transport") == "sparse"
                     and r["merge"] != "average"), default=0.0)
        worst_inter = min((r["inter_reduction_vs_dense"] for r in results
                           if r.get("transport") == "hier_sparse"
                           and r["merge"] != "average"), default=0.0)
        # adaptive invariant: dynamic total wire <= fixed at every quant
        worst_adapt = max((r["wire_vs_fixed"] for r in results
                           if r["arch"] == "comm_adapt"
                           and r["merge"] == "dynamic"), default=0.0)
        print(f"\n{len(results)} comm cells; sparse-vs-dense merge-wire "
              f"reduction (min over displacement schemes) = {worst:.2f}x, "
              f"inter-host tier-1 reduction = {worst_inter:.2f}x "
              f"(acceptance bars: both >= 4x at k/kappa <= 0.25); "
              f"dynamic-vs-fixed wire (max over quant levels) = "
              f"{worst_adapt:.2f}x (bar: <= 1.0)")
        return 0 if (worst >= 4.0 and worst_inter >= 4.0
                     and 0.0 < worst_adapt <= 1.0) else 1

    cells = []
    if args.all:
        for arch in registry.ARCH_IDS:
            for cell in registry.SHAPES:
                cells.append((arch, cell.name))
    else:
        if not args.arch or not args.shape:
            ap.error("need --arch and --shape (or --all)")
        cells = [(args.arch, args.shape)]

    meshes = [False, True] if (args.both_meshes or args.all) else \
        [args.multi_pod]
    results = []
    for arch, shape in cells:
        for mp in meshes:
            results.append(run_cell(arch, shape, multi_pod=mp,
                                    merge=args.merge, tau=args.tau,
                                    quantized=args.quantized))

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    existing = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            existing = json.load(f)
    keyf = lambda r: (r["arch"], r["shape"], r["mesh"],  # noqa: E731
                      r.get("merge", "none"), r.get("quantized", False),
                      r.get("transport", "none"))
    merged = {keyf(r): r for r in existing}
    for r in results:
        merged[keyf(r)] = r
    with open(args.out, "w") as f:
        json.dump(list(merged.values()), f, indent=1)

    bad = [r for r in results if r["status"] == "error"]
    print(f"\n{len(results)} cells: "
          f"{sum(r['status'] == 'ok' for r in results)} ok, "
          f"{sum(r['status'] == 'skipped' for r in results)} skipped, "
          f"{len(bad)} failed")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
