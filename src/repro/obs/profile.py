"""Roofline-attributed profiling: decompose measured wall into cost terms.

The paper's whole argument is a wall-clock accounting exercise — which
parallelization scheme wastes time where.  PR 6's spans say *how long* a
run took; this module says *why*: each run's measured wall is decomposed
per window against the three-term roofline

* ``compute``    — analytic device FLOPs for the VQ inner loop
  (``VqCell.window_flops``, the (d, kappa, tau, bm) hand count) over the
  chip's peak (``roofline.device_peaks``: the v5e's off-TPU),
* ``memory``     — analytic HBM traffic (``VqCell.window_hbm_bytes``)
  over HBM bandwidth,
* ``collective`` — merge bytes parsed out of the *actual compiled*
  program's post-SPMD HLO, trip-count-corrected for the window scan
  (``hlo_analysis.analyze_collectives``), over ICI link bandwidth,

plus an explicit ``host`` residual — whatever measured wall the modeled
terms do not explain (Python dispatch, transfers, the CPU backend being
nothing like a TPU).  The residual is *clamped at zero*: attribution can
under-explain wall (big host term) but the check gate fails when the
modeled terms overshoot the measured wall, which is what catches a wrong
analytic count or a mis-inferred trip count.

Wiring: ``MeshExecutor`` (and ``ElasticMeshExecutor``, which shares one
profiler across its per-M segment executors) calls

* ``record_program(key, hlo, cost)``  at each compile miss — the engine
  switches to AOT lowering when a profiler is attached so the compiled
  text comes from the very executable that then runs (zero extra
  compiles; the ``observe`` cache key already forks instrumented
  programs, profiling rides the same fork),
* ``note_segment(...)``               per executed run/segment with the
  (scheme, m, n_windows, d, kappa, tau, n_eval) shapes,
* ``finish_run(wall_s)``              once the run's wall is measured.

``finish_run`` emits ``roofline_efficiency{term=}`` gauges and
``attributed_*_ns`` counters through the shared ``MetricsRegistry`` and
appends an attribution record (exported by ``--profile PROF.json`` and
benchmarked by ``--suite profile``).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any

from repro.distributed import hlo_analysis
from repro.distributed.roofline import (VqCell, device_peaks,
                                        vq_roofline_terms)

TERMS = ("compute", "memory", "collective", "host")


@dataclasses.dataclass
class ProgramCost:
    """Cost facts parsed from one compiled mesh program."""

    key: str
    collective_bytes: float            # whole-program, trip-corrected
    bytes_by_kind: dict[str, float]
    loops: list[tuple[str, int]]       # (while body, trip count)
    cost_flops: float | None           # XLA cost_analysis (body counted once)
    cost_bytes: float | None

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class Profiler:
    """Per-run cost attribution against the three-term roofline.

    Opt-in and engine-agnostic: holds no jax state, only parsed HLO facts
    and shape metadata the engine reports.  Attach the run's
    ``MetricsRegistry`` to also publish gauges/counters.
    """

    def __init__(self, *, metrics=None):
        self.metrics = metrics
        self.peaks = device_peaks()
        self.programs: dict[str, ProgramCost] = {}
        self.attributions: list[dict] = []
        self._pending: list[dict] = []

    # -- engine-facing hooks -------------------------------------------------

    def record_program(self, key: Any, hlo_text: str, cost=None) -> ProgramCost:
        """Parse a freshly compiled program's HLO (called on compile miss)."""
        coll = hlo_analysis.analyze_collectives(hlo_text)
        flops = bytes_ = None
        if cost is not None:
            c0 = cost[0] if isinstance(cost, (list, tuple)) else cost
            if isinstance(c0, dict):
                flops = c0.get("flops")
                bytes_ = c0.get("bytes accessed")
        pc = ProgramCost(
            key=str(key),
            collective_bytes=float(coll["total_bytes"]),
            bytes_by_kind=dict(coll["bytes_by_kind"]),
            loops=list(coll["loops"]),
            cost_flops=flops, cost_bytes=bytes_)
        self.programs[pc.key] = pc
        return pc

    def note_segment(self, *, program: Any, scheme: str, transport: str,
                     topology: str, m: int, n_windows: int, d: int,
                     kappa: int, tau: int, n_eval: int = 0,
                     compiled: bool = False, subspaces: int = 1) -> None:
        """Report one executed segment's shapes (a whole run for the fixed-M
        executor; one per-M slice for an elastic run).  A product quantizer
        reports its codes per sub-codebook as ``kappa`` and its number of
        sub-codebooks as ``subspaces``: a step's search is then 2 * kappa
        * d operations, as a (kappa, d) codebook's is."""
        self._pending.append(dict(
            program=str(program), scheme=scheme, transport=transport,
            topology=topology, m=int(m), n_windows=max(int(n_windows), 1),
            d=int(d), kappa=int(kappa), tau=int(tau), n_eval=int(n_eval),
            compiled=bool(compiled), subspaces=int(subspaces)))

    def finish_run(self, wall_s: float) -> dict | None:
        """Attribute one run's measured wall across the pending segments.

        Per-window terms from each segment's ``VqCell`` (collective term
        from that segment's compiled program when recorded, analytic dense
        merge otherwise) are combined weighted by window count; the
        ``host`` term is the clamped residual, so
        ``sum(terms) == window wall`` exactly unless the model overshoots.
        """
        segs, self._pending = self._pending, []
        if not segs or wall_s <= 0:
            return None
        total_windows = sum(s["n_windows"] for s in segs)
        window_wall = wall_s / total_windows

        t = {"compute": 0.0, "memory": 0.0, "collective": 0.0}
        flops = hbm = coll_bytes = 0.0
        for s in segs:
            cell = VqCell(d=s["d"], kappa=s["kappa"], tau=s["tau"],
                          n_eval=s["n_eval"])
            prog = self.programs.get(s["program"])
            coll_per_win = (prog.collective_bytes / s["n_windows"]
                            if prog is not None else None)
            terms = vq_roofline_terms(
                cell, collective_bytes_per_window=coll_per_win,
                peaks=self.peaks)
            w = s["n_windows"] / total_windows
            for k in t:
                t[k] += terms[f"t_{k}"] * w
            flops += terms["window_flops"] * w
            hbm += terms["window_hbm_bytes"] * w
            coll_bytes += terms["collective_bytes"] * w

        modeled = sum(t.values())
        t["host"] = max(window_wall - modeled, 0.0)
        attributed = modeled + t["host"]
        consistency = abs(attributed - window_wall) / window_wall
        first = segs[0]
        rec = {
            "scheme": first["scheme"],
            "transport": first["transport"],
            "topology": first["topology"],
            "m": first["m"],
            "segments": len(segs),
            "n_windows": total_windows,
            "tau": first["tau"],
            "d": first["d"],
            "kappa": first["kappa"],
            "wall_s": wall_s,
            "window_wall_s": window_wall,
            **{f"t_{k}_s": v for k, v in t.items()},
            "attributed_window_s": attributed,
            "consistency": consistency,
            "efficiency": {k: (v / window_wall if window_wall > 0 else 0.0)
                           for k, v in t.items()},
            "window_flops": flops,
            "window_hbm_bytes": hbm,
            "collective_bytes_per_window": coll_bytes,
            "compiled_in_run": any(s["compiled"] for s in segs),
            "peaks": dataclasses.asdict(self.peaks),
        }
        self.attributions.append(rec)
        if self.metrics is not None:
            labels = {"scheme": first["scheme"],
                      "transport": first["transport"]}
            for k in TERMS:
                self.metrics.gauge("roofline_efficiency", term=k,
                                   **labels).set(rec["efficiency"][k])
                self.metrics.counter(f"attributed_{k}_ns", **labels).inc(
                    t[k] * total_windows * 1e9)
        return rec

    # -- export --------------------------------------------------------------

    def as_dict(self) -> dict:
        return {
            "attributions": self.attributions,
            "programs": {k: p.as_dict() for k, p in self.programs.items()},
        }

    def export_json(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.as_dict(), f, indent=1)

    def summary_table(self) -> str:
        """Aligned per-run attribution table (for ``--profile`` stdout)."""
        if not self.attributions:
            return "(no profiled runs)"
        hdr = (f"{'scheme':<12} {'wall_s':>9} {'win_us':>9} "
               f"{'compute%':>9} {'memory%':>8} {'collective%':>12} "
               f"{'host%':>7} {'consistency':>12}")
        lines = [hdr, "-" * len(hdr)]
        for r in self.attributions:
            eff = r["efficiency"]
            lines.append(
                f"{r['scheme']:<12} {r['wall_s']:>9.4f} "
                f"{r['window_wall_s'] * 1e6:>9.1f} "
                f"{eff['compute'] * 100:>8.3f}% {eff['memory'] * 100:>7.3f}% "
                f"{eff['collective'] * 100:>11.3f}% {eff['host'] * 100:>6.1f}% "
                f"{r['consistency']:>12.4f}")
        return "\n".join(lines)
