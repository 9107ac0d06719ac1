"""Drive the VQ train and serve paths once on a TPU and check what comes out.

    python chip_smoke.py                # one chip: train (window route and
                                        # per-step route), then serve
    python chip_smoke.py --four-chips   # only the 4-chip phase: xla vs ring
                                        # merges vs the sim oracle, and the
                                        # kappa-sharded lookup

Everything runs in this one process (a chip belongs to one process, so no
phase starts a child).  Every array is generated on the device from
``--seed``, shaped like SIFT1M (10^6 x 128 f32; TEXMEX, Jegou et al. 2011),
with codebook sizes after FAISS's IVF list counts (4 * sqrt(N) ~ 4096).

Each phase prints one ``phase {...}`` line: the route it took, compile and
run seconds, the device's peak bytes so far, whether its compiled program
holds a Pallas kernel (``tpu_custom_call``), and each check against a plain
reference with its tolerance.  The last line of standard output is the JSON
verdict.  The script exits non-zero, and prints no verdict, when JAX finds
no TPU, when it runs outside a checkout of the repo, or when a phase fails.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

N_POINTS = 1_000_000      # SIFT1M base set
DIM = 128                 # SIFT descriptor width
N_EVAL = 4096             # held-out points the engine scores every window
N_CENTERS = 4096          # mixture components the points are drawn around
TAU = 10
KAPPA_WINDOW = 1024       # fits the VMEM budget: the fused window kernel
KAPPA_STEP = 4096         # FAISS's 4*sqrt(N): just past the window budget
KAPPA_SHARDED = 32768     # 16 MiB of f32: past one core's VMEM budget
N_REQUESTS = 300
N_SHARDED_QUERIES = 1024

# -- tolerances, each with its reason -------------------------------------
# The TPU multiplies f32 operands at its default precision: one bf16 pass
# (a v5e returns serving distances off by up to 9e-4 of ||z||^2 + ||w||^2).
# Rounding both operands to bf16 (8 significant bits) moves each product
# z_i w_i by at most 2^-7 of itself, so the cross term z.w by at most
# 2^-8 (||z||^2 + ||w||^2), and a distance by at most 2^-7 of that scale.
TOL_DIST_SCALE = 2.0 ** -7
# A served code can then beat the true nearest one by at most twice that:
# both distances it was compared on may be off, in opposite directions.
TOL_CODE_GAP_SCALE = 2 * TOL_DIST_SCALE
# Mesh and sim run the same eq.-1/eq.-8 steps, but the Pallas kernels and
# XLA round differently, so a near-tie argmin can go either way and the
# trajectories part there.  Such a flip moves one of two almost equally
# near codes instead of the other, which barely moves the final distortion
# (a v5e: 5e-9 of it at kappa=1,024, 5e-6 at kappa=4,096, 10^6 points).
# 1e-3 admits that with room and still fails a path that skips updates
# (training moves the kappa=4,096 distortion by 5e-3).
TOL_SIM_REL = 1e-3
# The xla and ring merges sum the same deltas in another order: the same
# near-tie argument as above.
TOL_TRANSPORT_REL = 1e-3

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _fail(msg: str) -> int:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    return 1


class _Clock:
    """Backend compile seconds, summed from JAX's monitoring events."""

    def __init__(self, jax):
        self.compile_s = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.compile_s += duration
            self.compiles += 1


def _f64_sqdist(z, w):
    """Exact (n, kappa) squared distances in float64, in row chunks."""
    import numpy as np

    z = np.asarray(z, np.float64)
    w = np.asarray(w, np.float64)
    w2 = (w * w).sum(1)
    out = np.empty((z.shape[0], w.shape[0]))
    for i in range(0, z.shape[0], 512):
        zc = z[i:i + 512]
        out[i:i + 512] = (zc * zc).sum(1)[:, None] - 2.0 * zc @ w.T + w2
    return np.maximum(out, 0.0)


def _f64_distortion(z, w) -> float:
    return float(_f64_sqdist(z, w).min(1).mean())


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


class _Phase:
    """One phase's line: measurements plus named checks."""

    def __init__(self, name: str, clock: _Clock, device):
        self.rec = {"phase": name}
        self.ok = True
        self.clock = clock
        self.device = device

    def check(self, name: str, passed: bool, **values) -> None:
        self.rec[name] = {**values, "ok": bool(passed)}
        self.ok &= bool(passed)

    def close(self) -> bool:
        stats = self.device.memory_stats() or {}
        self.rec["peak_bytes"] = stats.get("peak_bytes_in_use")
        self.rec["ok"] = self.ok
        print("phase " + json.dumps(self.rec), flush=True)
        return self.ok


def _kernel_check(ph: _Phase, text: str, want: tuple[str, ...],
                  absent: tuple[str, ...] = ()) -> None:
    """The compiled program holds Pallas kernels, and the expected ones."""
    ph.rec["tpu_custom_call"] = "tpu_custom_call" in text
    ph.check("kernels", ph.rec["tpu_custom_call"]
             and all(f"/{k}/" in text for k in want)
             and not any(f"/{k}/" in text for k in absent),
             want=list(want), absent=list(absent))


def _train(jax, ph: _Phase, ex, w0, data, ev, sim_w=None):
    """Run the mesh executor twice (cold, then from its compile cache) and
    check the result against float64 and, where given, the sim oracle."""
    import numpy as np

    c0, n0 = ph.clock.compile_s, ph.clock.compiles
    t0 = time.perf_counter()
    res = ex.run("delta", w0, data, ev, tau=TAU)
    jax.block_until_ready(res.w_shared)
    cold_s = time.perf_counter() - t0
    ph.rec["compile_s"] = ph.clock.compile_s - c0
    n1 = ph.clock.compiles
    t0 = time.perf_counter()
    again = ex.run("delta", w0, data, ev, tau=TAU)
    jax.block_until_ready(again.w_shared)
    ph.rec["run_s"] = time.perf_counter() - t0
    ph.rec["cold_s"] = cold_s
    ph.check("warm_rerun", ph.clock.compiles == n1 and np.array_equal(
        np.asarray(again.w_shared), np.asarray(res.w_shared)),
        compiles=ph.clock.compiles - n1, first_run_compiles=n1 - n0)
    w = np.asarray(res.w_shared)
    ev_host = np.asarray(ev).reshape(-1, ev.shape[-1])
    d2 = _f64_sqdist(ev_host, w)
    exact = float(d2.min(1).mean())
    start = _f64_distortion(ev_host, np.asarray(w0))
    reported = float(np.asarray(res.distortion)[-1])
    ph.check("trained", np.isfinite(w).all() and exact < start,
             f64_start=start, f64_final=exact)
    # each point's distance may be off by TOL_DIST_SCALE of its scale
    scale = float(((ev_host.astype(np.float64) ** 2).sum(1)
                   + (w.astype(np.float64) ** 2).sum(1)[d2.argmin(1)]).mean())
    ph.check("eval_vs_f64", abs(reported - exact) <= TOL_DIST_SCALE * scale,
             device=reported, f64=exact, err_over_scale=abs(
                 reported - exact) / scale, tol=TOL_DIST_SCALE)
    if sim_w is not None:
        sim = _f64_distortion(ev_host, np.asarray(sim_w))
        ph.check("vs_sim", _rel(exact, sim) <= TOL_SIM_REL, sim_f64=sim,
                 rel=_rel(exact, sim), tol=TOL_SIM_REL)
    return res, exact


def _check_codes(ph: _Phase, z, w, got, mind) -> None:
    """Served codes and their distances against float64, within the bf16
    bounds above (``scale`` is each row's ||z||^2 + ||w||^2)."""
    import numpy as np

    d2 = _f64_sqdist(z, w)
    rows = np.arange(len(z))
    gap = d2[rows, got] - d2.min(1)
    scale = ((np.asarray(z, np.float64) ** 2).sum(1)
             + (np.asarray(w, np.float64) ** 2).sum(1)[got])
    ph.check("codes_vs_f64", bool((gap <= TOL_CODE_GAP_SCALE * scale).all()),
             rows=len(z), exact_frac=float((gap == 0).mean()),
             max_gap_over_scale=float((gap / scale).max()),
             tol=TOL_CODE_GAP_SCALE)
    derr = np.abs(mind - d2[rows, got]) / scale
    ph.check("mindist_vs_f64", bool((derr <= TOL_DIST_SCALE).all()),
             max_err_over_scale=float(derr.max()), tol=TOL_DIST_SCALE)


def _make_data(jax, seed: int):
    """(N_POINTS, DIM) stream and (N_EVAL, DIM) held-out points, on device."""
    from repro.data import synthetic

    allpts = synthetic.mixture_data(jax.random.PRNGKey(seed),
                                    n=N_POINTS + N_EVAL, d=DIM,
                                    n_centers=N_CENTERS)
    return allpts[:N_POINTS], allpts[N_POINTS:]


def _text_profiler():
    """A Profiler that also keeps each compiled mesh program's text (the
    engine hands a profiler the HLO of the very executable it runs)."""
    from repro.obs import Profiler

    class TextProfiler(Profiler):
        def __init__(self):
            super().__init__()
            self.texts = []

        def record_program(self, key, hlo_text, cost=None):
            self.texts.append(hlo_text)
            return super().record_program(key, hlo_text, cost)

    return TextProfiler()


def one_chip(jax, clock, seed: int) -> bool:
    import numpy as np

    from repro.data import synthetic
    from repro.engine import MeshExecutor, SimExecutor
    from repro.kernels import ops
    from repro.serve import CodebookStore, QuantizeService, ShardedLookup

    dev = jax.devices()[0]
    t0 = time.perf_counter()
    data, ev = _make_data(jax, seed)
    jax.block_until_ready(data)
    print(f"data: {data.shape} + {ev.shape} f32 on {dev.device_kind} in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    data3, ev3 = data[None], ev[None]
    kw = jax.random.PRNGKey(seed + 1)
    ok = True
    trained = None
    for name, kappa in (("train_window", KAPPA_WINDOW),
                        ("train_per_step", KAPPA_STEP)):
        ph = _Phase(name, clock, dev)
        route = ("window" if ops.window_fits_vmem(kappa, DIM, TAU)
                 else "per_step")
        ph.rec.update(route=route, m=1, kappa=kappa, d=DIM, tau=TAU,
                      points=N_POINTS, window_vmem_bytes=ops.window_vmem_bytes(
                          kappa, DIM, TAU))
        w0 = synthetic.kmeanspp_init(kw, data, kappa)
        sim = SimExecutor().run("delta", w0, data3, ev3, tau=TAU)
        prof = _text_profiler()
        ex = MeshExecutor(profiler=prof)
        res, _ = _train(jax, ph, ex, w0, data3, ev3, sim_w=sim.w_shared)
        want = ("vq_window",) if route == "window" else ("vq_assign",)
        absent = (("vq_delta", "vq_assign") if route == "window"
                  else ("vq_delta", "vq_window"))
        _kernel_check(ph, "\n".join(prof.texts), want, absent)
        ok &= ph.close()
        trained = res.w_shared

    # serve the trained kappa=4096 codebook through the micro-batcher
    ph = _Phase("serve", clock, dev)
    lookup = ShardedLookup(n_devices=1, mode="direct")
    store = CodebookStore(trained)
    w = store.latest().w
    ph.rec.update(route=lookup.plan(*w.shape), kappa=w.shape[0], d=DIM,
                  requests=N_REQUESTS)
    rng = np.random.default_rng(seed)
    ev_host = np.asarray(ev)
    sizes = rng.integers(1, 65, size=N_REQUESTS)
    starts = rng.integers(0, N_EVAL - 64, size=N_REQUESTS)
    queries = [ev_host[s:s + k] for s, k in zip(starts, sizes)]
    c0 = clock.compile_s
    with QuantizeService(store, lookup) as svc:
        ph.rec["compile_s"] = clock.compile_s - c0
        t0 = time.perf_counter()
        futs = [svc.submit(q) for q in queries]
        resps = [f.result(timeout=300) for f in futs]
        ph.rec["run_s"] = time.perf_counter() - t0
        ph.rec["flushes"] = svc.stats.flushes
    z = np.concatenate(queries)
    _check_codes(ph, z, w, np.concatenate([r.assign for r in resps]),
                 np.concatenate([r.mindist for r in resps]))
    text = jax.jit(lookup.assign).lower(z[:128], w).compile().as_text()
    _kernel_check(ph, text, ("vq_assign",))
    ok &= ph.close()
    return ok


def four_chips(jax, clock, seed: int) -> bool:
    import numpy as np

    from repro.comm import RingTransport
    from repro.data import synthetic
    from repro.engine import MeshExecutor, SimExecutor
    from repro.serve import ShardedLookup

    m = 4
    dev = jax.devices()[0]
    data, ev = _make_data(jax, seed)
    data4 = synthetic.split_workers(data, m)
    ev4 = synthetic.split_workers(ev, m)
    kw = jax.random.PRNGKey(seed + 1)
    w0 = synthetic.kmeanspp_init(kw, data, KAPPA_WINDOW)
    sim = SimExecutor().run("delta", w0, data4, ev4, tau=TAU)
    ok = True
    finals = {}
    for transport in ("xla", "ring"):
        ph = _Phase(f"mesh4_{transport}", clock, dev)
        ph.rec.update(m=m, kappa=KAPPA_WINDOW, d=DIM, tau=TAU,
                      points_per_worker=data4.shape[1], transport=transport)
        if transport == "ring":
            t = RingTransport()
            ph.check("ring_on_pallas", t._pallas_ok())
        else:
            t = transport
        prof = _text_profiler()
        res, finals[transport] = _train(
            jax, ph, MeshExecutor(transport=t, profiler=prof), w0, data4,
            ev4, sim_w=sim.w_shared)
        text = "\n".join(prof.texts)
        want = ("vq_window",) + (("ring_all_reduce",) if transport == "ring"
                                 else ())
        _kernel_check(ph, text, want)
        devices = {d.id for d in res.w_shared.sharding.device_set}
        ph.check("four_devices", len(devices) == m and (
            transport == "ring" or "all-reduce" in text),
            device_ids=sorted(devices))
        ok &= ph.close()

    ph = _Phase("mesh4_xla_vs_ring", clock, dev)
    r = _rel(finals["ring"], finals["xla"])
    ph.check("ring_vs_xla", r <= TOL_TRANSPORT_REL, xla_f64=finals["xla"],
             ring_f64=finals["ring"], rel=r, tol=TOL_TRANSPORT_REL)
    ok &= ph.close()

    ph = _Phase("lookup4_shard_kappa", clock, dev)
    lookup = ShardedLookup(n_devices=m, mode="shard_kappa")
    w = synthetic.kmeanspp_init(jax.random.PRNGKey(seed + 2), data,
                                KAPPA_SHARDED)
    z = ev[:N_SHARDED_QUERIES]
    ph.rec.update(route=lookup.plan(*w.shape), kappa=KAPPA_SHARDED, d=DIM,
                  queries=N_SHARDED_QUERIES)
    c0 = clock.compile_s
    jax.block_until_ready(lookup.assign(z, w))
    ph.rec["compile_s"] = clock.compile_s - c0
    t0 = time.perf_counter()
    got, mind = jax.block_until_ready(lookup.assign(z, w))
    ph.rec["run_s"] = time.perf_counter() - t0
    _check_codes(ph, np.asarray(z), np.asarray(w), np.asarray(got),
                 np.asarray(mind))
    text = jax.jit(lookup.assign).lower(z, w).compile().as_text()
    _kernel_check(ph, text, ("vq_assign",))
    ph.check("collective", "all-reduce" in text)
    ok &= ph.close()
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip phase (needs 4 TPU chips)")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro import compile_cache
        from repro.kernels import ops
    except ImportError as e:
        return _fail(f"run from a checkout of the repo ({e})")
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        return _fail(f"JAX found no devices: {e}")
    dev = devices[0]
    if dev.platform != "tpu":
        return _fail(f"no TPU: JAX's first device is {dev.platform!r}")
    if ops._interpret_default():
        return _fail("the Pallas kernels would run in interpret mode")
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        return _fail(f"needs {need} TPU chips, JAX found {len(devices)}")
    compile_cache.enable()
    clock = _Clock(jax)

    run = four_chips if args.four_chips else one_chip
    if not run(jax, clock, args.seed):
        return _fail("a phase failed its check (see the phase lines)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
