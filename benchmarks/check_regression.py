"""CI benchmark regression gate for the engine and serve suites.

The suite is auto-detected from the baseline JSON's ``suite`` field.

**engine**: diffs a fresh ``benchmarks.run --suite engine --quick`` output
against the committed ``BENCH_engine.json`` baseline and FAILS (exit 1)
when:

  * the mesh-vs-sim wall-clock ratio regresses by more than
    ``--max-ratio-regression`` on every M leg (default 1.25, i.e. >25%
    slower relative to the sim executor on the same machine — absolute wall
    times are not comparable across machines, the ratio is); or
  * any distortion curve diverges from the baseline beyond ``--curve-rtol``
    (the runs are seeded, so the curves are a numerical fingerprint of the
    engine — a drift means the schemes no longer compute what they did).

The mesh/sim ratio normalizes the machine out of the comparison as far as
one number can: both executors ran the same work on the same box.  It is
still mildly hardware-shaped (core count vs the 8 forced devices), so if
the gate reads persistently high or low on a new runner class with no code
change, regenerate the committed baseline THERE (`python -m benchmarks.run
--suite engine --quick`) rather than widening the threshold — the printed
per-side medians make the two cases easy to tell apart.

**serve**: diffs a fresh ``--suite serve --quick`` output against the
committed ``BENCH_serve.json`` and FAILS when:

  * the micro-batching speedup (batched sharded-lookup rows/s over the
    unbatched single-dispatch figure, both measured on the same box — the
    serve analogue of the engine's machine-normalizing mesh/sim ratio)
    regresses by more than ``--max-ratio-regression``; or
  * the speedup drops below ``--min-speedup`` (default 4x, the serving
    acceptance bar); or
  * the hot-swap leg failed any request or served non-monotonic codebook
    versions (functional, machine-independent).

**hier**: diffs a fresh ``--suite hier --quick`` output against the
committed ``BENCH_hier.json`` and FAILS when:

  * any cell's measured per-tier merge wire bytes (intra-host tier 0,
    inter-host tier 1) differ from the baseline (trace-exact, like comm);
  * a hierarchical-dense cell no longer bit-matches the flat reference
    (``bitmatch_flat`` — the tentpole's oracle-equivalence contract); or
  * the inter-host sparse-vs-dense tier-1 wire reduction drops below
    ``--min-sparse-reduction`` (default 4x at k/kappa = 0.25); or
  * the hier-dense-vs-flat wall parity (same box, machine divides out)
    regresses by more than ``--max-ratio-regression`` (min over scheme
    legs); or any final distortion diverges beyond ``--curve-rtol``.

**comm**: diffs a fresh ``--suite comm --quick`` output against the
committed ``BENCH_comm.json`` and FAILS when:

  * any cell's measured merge wire bytes differ from the baseline (the
    bytes are trace-exact shape arithmetic — drift means the accounting or
    the schemes' collective structure changed); or
  * the sparse-vs-dense wire reduction drops below ``--min-sparse-reduction``
    (default 4x, the ISSUE-4 acceptance bar at k/kappa = 0.25); or
  * the ring-vs-xla wall parity (same box, machine divides out) regresses
    by more than ``--max-ratio-regression``; or any final distortion
    diverges beyond ``--curve-rtol``.

**obs**: diffs a fresh ``--suite obs --quick`` output against the
committed ``BENCH_obs.json`` and FAILS when:

  * any scheme's live-instrumentation overhead (tracer + metrics enabled
    but unexported, over the bare executor on the same box — the machine
    divides out of the on/off ratio) exceeds ``--max-obs-overhead``
    (default 1.03, the <3%% acceptance bar; absolute, not
    baseline-relative); or
  * the traced 2-host hierarchical run no longer passes the
    ``repro.obs.check`` invariants with tier-0 AND tier-1 merge spans and
    the ``codebook_divergence`` counter present (functional,
    machine-independent).

**chaos**: diffs a fresh ``--suite chaos --quick`` output against the
committed ``BENCH_chaos.json`` and FAILS when:

  * the seeded kill/slow/partition event schedule differs from the
    baseline (the same seed MUST draw the identical events on every
    device count — the chaos suite's determinism pin);
  * the quorum-merge wire bytes differ (trace-exact, like comm/hier);
  * the faulted run's final distortion over the fault-free oracle
    exceeds ``--max-chaos-distortion`` (default 1.25 — the acceptance
    bound: surviving 2 kills + 1 straggler + 1 partition costs < 25%%
    distortion); or the final distortion diverges from the baseline
    beyond ``--curve-rtol``; or the chaos trace (``chaos_*`` spans)
    violated the ``repro.obs.check`` invariants.

  ``--absolute`` gates the FRESH output alone on the absolute bars
  (distortion bound + trace invariants) with no baseline file — the
  cron seed sweep runs seeds that have no committed baseline.

**profile**: diffs a fresh ``--suite profile --quick`` output against the
committed ``BENCH_profile.json`` and FAILS when:

  * any scheme's roofline attribution terms (compute + memory +
    collective + host residual) no longer sum to the measured per-window
    wall within ``--max-consistency`` (default 0.15 — since the host
    residual is clamped at zero, a violation means the ANALYTIC terms
    overshoot the measurement: wrong flop/byte counts or a mis-inferred
    while-loop trip count);
  * the compute-term roofline efficiency drops below
    ``--min-compute-eff`` (attribution lost the analytic compute term);
  * the trip-count-corrected HLO collective bytes per window drift from
    the baseline (machine-independent shape arithmetic, pinned exactly);
  * the HLO bytes disagree with the transport's own CommLog logical-byte
    accounting of the same program (two independent derivations of the
    same traffic must agree).

  On any profile failure the per-term attribution deltas vs the baseline
  are printed; when any OTHER suite's gate fails and a
  ``BENCH_profile.fresh.json`` sits beside the fresh file, the same
  deltas are printed as a diagnostic — the gate says which roofline term
  the regression lives in, not just that wall moved.

Every run (pass or fail) ends with a gate table listing each gate's
measured value, its bar, and its margin — so CI logs always show how
close every suite sits to its thresholds, not only when one trips.

All suites additionally WARN (never fail) when the baseline's recorded
per-iteration ``wall_samples`` spread exceeds the regression threshold:
a ratio FAIL against such a baseline is as likely noise as regression,
so the fix is regenerating the baseline on a quieter box, not widening
the gate.

Exit codes: 0 pass, 1 regression, 2 usage/config mismatch (e.g. the fresh
run used a different n/tau/d than the baseline — the comparison would be
meaningless, so that is an error, not a pass), 3 baseline or fresh file
missing/unreadable (a SETUP failure, distinct from a perf regression so
CI can route it to the right owner).

    python -m benchmarks.check_regression \
        --baseline BENCH_engine.json --fresh BENCH_engine.fresh.json
    python -m benchmarks.check_regression \
        --baseline BENCH_serve.json --fresh BENCH_serve.fresh.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np


def _gate(gates: list | None, name: str, value: float, bar: float,
          cmp: str = "<=") -> None:
    """Record one gate's (value, bar) pair for the always-printed summary
    table — the margin to every bar should be visible in CI logs on green
    runs too, not only when something trips."""
    if gates is not None:
        gates.append({"name": name, "value": float(value), "bar": float(bar),
                      "cmp": cmp})


def _gate_ok(g: dict) -> bool:
    if g["cmp"] == "<=":
        return g["value"] <= g["bar"]
    if g["cmp"] == ">=":
        return g["value"] >= g["bar"]
    return g["value"] == g["bar"]


def gate_table(gates: list[dict]) -> str:
    """Aligned gate | value | bar | status summary."""
    if not gates:
        return ""
    name_w = max(len(g["name"]) for g in gates)
    lines = [f"{'gate':<{name_w}}  {'value':>12}  {'bar':>12}  status",
             "-" * (name_w + 38)]
    for g in gates:
        lines.append(
            f"{g['name']:<{name_w}}  {g['value']:>12.6g}  "
            f"{g['cmp']:>2} {g['bar']:>9.6g}  "
            f"{'ok' if _gate_ok(g) else 'FAIL'}")
    return "\n".join(lines)


def _index(doc: dict) -> dict[tuple[str, int], dict]:
    return {(r["executor"], r["m"]): r for r in doc.get("results", [])}


def _config_key(rec: dict) -> tuple:
    return tuple(rec.get(k) for k in ("scheme", "n", "d", "kappa", "tau"))


def check(baseline: dict, fresh: dict, *, max_ratio_regression: float = 1.25,
          curve_rtol: float = 1e-2,
          gates: list | None = None) -> tuple[bool, list[str]]:
    """Returns (ok, messages).  Raises ValueError on config mismatch."""
    base_idx, fresh_idx = _index(baseline), _index(fresh)
    common = sorted(set(base_idx) & set(fresh_idx))
    if not common:
        raise ValueError("no (executor, M) records shared between baseline "
                         "and fresh output — nothing to compare")
    msgs: list[str] = []
    ok = True

    # -- wall clock: per-M mesh/sim ratios, gated on the MINIMUM regression
    # over M.  The ratio normalizes out the machine (both executors ran on
    # the same box); the min is the flap-proof statistic on an oversubscribed
    # CI host (8 forced devices on 2 cores jitter individual legs >2x) —
    # a genuine engine regression slows EVERY M leg, noise does not.
    ms = [m for (ex, m) in common if ex == "mesh"
          and ("sim", m) in base_idx and ("sim", m) in fresh_idx]
    if ms:
        def ratios(idx):
            return np.asarray([
                idx[("mesh", m)]["wall_s"]
                / max(idx[("sim", m)]["wall_s"], 1e-12) for m in ms])
        r_base, r_fresh = ratios(base_idx), ratios(fresh_idx)
        regress = float(np.min(r_fresh / r_base))
        _gate(gates, "engine mesh/sim min wall regression", regress,
              max_ratio_regression)
        line = (f"mesh/sim wall ratio over M={ms}: baseline median "
                f"{float(np.median(r_base)):.2f}x, fresh "
                f"{float(np.median(r_fresh)):.2f}x "
                f"(min per-M regression {regress:.2f}x)")
        if regress > max_ratio_regression:
            ok = False
            msgs.append(f"FAIL {line} > {max_ratio_regression:.2f}x allowed")
        else:
            msgs.append(f"ok   {line}")

    # -- distortion curves: numerical fingerprint of the engine
    max_err = 0.0
    for key in common:
        b, f = base_idx[key], fresh_idx[key]
        if _config_key(b) != _config_key(f):
            raise ValueError(
                f"{key}: baseline config {_config_key(b)} != fresh "
                f"{_config_key(f)} — regenerate the baseline "
                f"(benchmarks.run --suite engine --quick) instead of "
                f"comparing different runs")
        cb = np.asarray(b["distortion"], np.float64)
        cf = np.asarray(f["distortion"], np.float64)
        if cb.shape != cf.shape:
            raise ValueError(
                f"{key}: curve length {cf.shape} != baseline {cb.shape} "
                f"— config mismatch")
        err = float(np.max(np.abs(cf - cb) / (np.abs(cb) + 1e-12)))
        max_err = max(max_err, err)
        if err > curve_rtol:
            ok = False
            msgs.append(f"FAIL {key}: distortion curve diverged "
                        f"(max rel err {err:.2e} > {curve_rtol:.0e})")
        else:
            msgs.append(f"ok   {key}: curve max rel err {err:.2e}")
    _gate(gates, "engine distortion curve max rel err", max_err, curve_rtol)

    return ok, msgs


def _serve_rec(doc: dict, kind: str) -> dict | None:
    recs = [r for r in doc.get("results", []) if r.get("kind") == kind]
    return recs[-1] if recs else None


def check_serve(baseline: dict, fresh: dict, *,
                max_ratio_regression: float = 1.25,
                min_speedup: float = 4.0,
                gates: list | None = None) -> tuple[bool, list[str]]:
    """Serve-suite gate; same contract as ``check``."""
    msgs: list[str] = []
    ok = True
    b_sp, f_sp = _serve_rec(baseline, "speedup"), _serve_rec(fresh, "speedup")
    if b_sp is None or f_sp is None:
        raise ValueError("serve suite needs a 'speedup' record in both "
                         "baseline and fresh output — regenerate with "
                         "benchmarks.run --suite serve")
    for k in ("m", "kappa", "d"):
        if b_sp.get(k) != f_sp.get(k):
            raise ValueError(
                f"speedup config mismatch on {k}: baseline {b_sp.get(k)} != "
                f"fresh {f_sp.get(k)} — regenerate the baseline instead of "
                f"comparing different runs")
    # the speedup is unbatched-vs-batched on ONE box, so (like the engine's
    # mesh/sim wall ratio) the machine divides out of the comparison
    regress = b_sp["speedup"] / max(f_sp["speedup"], 1e-12)
    _gate(gates, "serve speedup regression", regress, max_ratio_regression)
    _gate(gates, "serve batched speedup", f_sp["speedup"], min_speedup, ">=")
    line = (f"micro-batch speedup: baseline {b_sp['speedup']:.1f}x, "
            f"fresh {f_sp['speedup']:.1f}x (regression {regress:.2f}x)")
    if regress > max_ratio_regression:
        ok = False
        msgs.append(f"FAIL {line} > {max_ratio_regression:.2f}x allowed")
    elif f_sp["speedup"] < min_speedup:
        ok = False
        msgs.append(f"FAIL {line}; fresh speedup below the "
                    f"{min_speedup:.0f}x serving bar")
    else:
        msgs.append(f"ok   {line}")

    hot = _serve_rec(fresh, "hotswap")
    _gate(gates, "serve hot-swap failed requests",
          hot.get("failed", 1) if hot else 1, 0)
    if hot is None:
        ok = False
        msgs.append("FAIL fresh serve run has no hotswap record")
    elif hot.get("failed", 1) or not hot.get("versions_monotonic", False):
        ok = False
        msgs.append(f"FAIL hot-swap under load: failed={hot.get('failed')} "
                    f"monotonic={hot.get('versions_monotonic')}")
    else:
        msgs.append(
            f"ok   hot-swap under load: 0 failed, served versions "
            f"{hot['versions_served'][0]}..{hot['versions_served'][1]} "
            f"monotonic (staleness_max={hot.get('staleness_max')})")
    return ok, msgs


def _comm_cells(doc: dict) -> dict[tuple[str, str], dict]:
    return {(r["scheme"], r["transport"]): r
            for r in doc.get("results", []) if r.get("kind") == "cell"}


def check_comm(baseline: dict, fresh: dict, *,
               max_ratio_regression: float = 1.25,
               min_sparse_reduction: float = 4.0,
               curve_rtol: float = 1e-2,
               gates: list | None = None) -> tuple[bool, list[str]]:
    """Comm-suite gate; same contract as ``check``.

    Wire bytes are trace-exact shape arithmetic, so they must match the
    baseline EXACTLY — any drift means the accounting (or the schemes'
    collective structure) changed, which is the thing this suite pins.
    Wall gates ride ratios measured on one box (machine divides out):
    ring-vs-xla parity and its regression vs the baseline ratio.
    """
    msgs: list[str] = []
    ok = True
    b_cells, f_cells = _comm_cells(baseline), _comm_cells(fresh)
    missing = sorted(set(b_cells) - set(f_cells))
    if missing:
        # a vanished cell is lost coverage, not a pass: every baseline
        # (scheme, transport) pin must still be produced by the fresh run
        raise ValueError(
            f"fresh comm run is missing baseline cells {missing} — the "
            f"sweep lost coverage (regenerate the baseline only if the "
            f"cell was removed on purpose)")
    common = sorted(set(b_cells) & set(f_cells))
    if not common:
        raise ValueError("no (scheme, transport) cells shared between "
                         "baseline and fresh comm output — regenerate with "
                         "benchmarks.run --suite comm")
    drifted = 0
    max_err = 0.0
    for key in common:
        b, f = b_cells[key], f_cells[key]
        cfg = ("m", "n", "d", "kappa", "tau", "sparse_frac")
        if tuple(b.get(k) for k in cfg) != tuple(f.get(k) for k in cfg):
            raise ValueError(
                f"{key}: baseline config != fresh — regenerate the "
                f"baseline (benchmarks.run --suite comm) instead of "
                f"comparing different runs")
        if b["merge_wire_bytes"] != f["merge_wire_bytes"]:
            ok = False
            drifted += 1
            msgs.append(
                f"FAIL {key}: measured merge wire bytes drifted "
                f"{b['merge_wire_bytes']} -> {f['merge_wire_bytes']} "
                f"(accounting or collective structure changed)")
        else:
            msgs.append(f"ok   {key}: merge wire "
                        f"{f['merge_wire_bytes']} B (exact)")
        err = abs(f["final_C"] - b["final_C"]) / (abs(b["final_C"]) + 1e-12)
        max_err = max(max_err, err)
        if err > curve_rtol:
            ok = False
            msgs.append(f"FAIL {key}: final distortion diverged "
                        f"(rel err {err:.2e} > {curve_rtol:.0e})")
    _gate(gates, "comm wire-byte cells drifted", drifted, 0)
    _gate(gates, "comm final distortion max rel err", max_err, curve_rtol)

    red_ok, red_msgs = _check_reduction_record(
        baseline, fresh, kind="sparse_reduction", suite="comm",
        label="sparse-vs-dense wire reduction", floor=min_sparse_reduction,
        gates=gates)
    par_ok, par_msgs = _check_parity_record(
        baseline, fresh, kind="ring_parity", label="ring/xla wall parity",
        max_ratio_regression=max_ratio_regression, gates=gates)
    return ok and red_ok and par_ok, msgs + red_msgs + par_msgs


def _check_reduction_record(baseline: dict, fresh: dict, *, kind: str,
                            suite: str, label: str, floor: float,
                            gates: list | None = None
                            ) -> tuple[bool, list[str]]:
    """Shared floor gate on a wire-reduction record (comm + hier suites)."""
    b_red = _serve_rec(baseline, kind)
    f_red = _serve_rec(fresh, kind)
    if f_red is None or b_red is None:
        return False, [f"FAIL {suite} suite needs a {kind!r} record in "
                       f"both baseline and fresh output"]
    _gate(gates, label, f_red["reduction"], floor, ">=")
    if f_red["reduction"] < floor:
        return False, [f"FAIL {label} {f_red['reduction']:.2f}x below the "
                       f"{floor:.0f}x bar"]
    return True, [f"ok   {label} {f_red['reduction']:.2f}x "
                  f"(bar {floor:.0f}x)"]


def _check_parity_record(baseline: dict, fresh: dict, *, kind: str,
                         label: str, max_ratio_regression: float,
                         gates: list | None = None
                         ) -> tuple[bool, list[str]]:
    """Shared wall-parity gate: MIN regression over the scheme legs (the
    engine gate's flap-proof statistic — noise on an oversubscribed host
    jitters single legs, a genuine slowdown hits all of them)."""
    b_par = _serve_rec(baseline, kind)
    f_par = _serve_rec(fresh, kind)
    if f_par is None or b_par is None:
        return False, [f"FAIL suite needs a {kind!r} record in both "
                       f"baseline and fresh output"]
    schemes = sorted(set(b_par["parity"]) & set(f_par["parity"]))
    if not schemes:
        raise ValueError(f"{kind} records share no scheme legs — "
                         f"regenerate the baseline")
    regress = min(f_par["parity"][s] / max(b_par["parity"][s], 1e-12)
                  for s in schemes)
    _gate(gates, f"{label} min regression", regress, max_ratio_regression)
    med_b = float(np.median([b_par["parity"][s] for s in schemes]))
    med_f = float(np.median([f_par["parity"][s] for s in schemes]))
    line = (f"{label} over {schemes}: baseline median {med_b:.2f}x, "
            f"fresh {med_f:.2f}x (min per-scheme regression {regress:.2f}x)")
    if regress > max_ratio_regression:
        return False, [f"FAIL {line} > {max_ratio_regression:.2f}x allowed"]
    return True, [f"ok   {line}"]


def _hier_cells(doc: dict) -> dict[tuple[str, str], dict]:
    return {(r["scheme"], r["variant"]): r
            for r in doc.get("results", []) if r.get("kind") == "cell"}


def check_hier(baseline: dict, fresh: dict, *,
               max_ratio_regression: float = 1.25,
               min_sparse_reduction: float = 4.0,
               curve_rtol: float = 1e-2,
               gates: list | None = None) -> tuple[bool, list[str]]:
    """Hier-suite gate; same contract as ``check``.

    Per-tier wire bytes are trace-exact shape arithmetic, so they must
    match the baseline EXACTLY; the dense-tier-1 bit-match flag is the
    tentpole's flat-oracle equivalence and must stay True on every scheme.
    """
    msgs: list[str] = []
    ok = True
    b_cells, f_cells = _hier_cells(baseline), _hier_cells(fresh)
    missing = sorted(set(b_cells) - set(f_cells))
    if missing:
        raise ValueError(
            f"fresh hier run is missing baseline cells {missing} — the "
            f"sweep lost coverage (regenerate the baseline only if the "
            f"cell was removed on purpose)")
    common = sorted(set(b_cells) & set(f_cells))
    if not common:
        raise ValueError("no (scheme, variant) cells shared between "
                         "baseline and fresh hier output — regenerate with "
                         "benchmarks.run --suite hier")
    drifted = 0
    max_err = 0.0
    for key in common:
        b, f = b_cells[key], f_cells[key]
        cfg = ("m", "hosts", "workers_per_host", "n", "d", "kappa", "tau",
               "tier1_frac")
        if tuple(b.get(k) for k in cfg) != tuple(f.get(k) for k in cfg):
            raise ValueError(
                f"{key}: baseline config != fresh — regenerate the "
                f"baseline (benchmarks.run --suite hier) instead of "
                f"comparing different runs")
        # total merge bytes too, not just the tiered split — the flat
        # cells have no tiers, and their accounting is pinned HERE
        drift = [(t, b.get(t, 0), f.get(t, 0))
                 for t in ("merge_wire_bytes", "tier0_wire_bytes",
                           "tier1_wire_bytes")
                 if b.get(t, 0) != f.get(t, 0)]
        if drift:
            ok = False
            drifted += len(drift)
            for t, bb, ff in drift:
                msgs.append(
                    f"FAIL {key}: measured {t} drifted {bb} -> {ff} "
                    f"(accounting or collective structure changed)")
        else:
            msgs.append(
                f"ok   {key}: merge {f.get('merge_wire_bytes', 0)} B "
                f"(intra {f.get('tier0_wire_bytes', 0)} B / "
                f"inter {f.get('tier1_wire_bytes', 0)} B, exact)")
        if key[1] == "hier_dense" and not f.get("bitmatch_flat", False):
            ok = False
            msgs.append(f"FAIL {key}: dense tier-1 run no longer "
                        f"bit-matches the flat mesh oracle")
        err = abs(f["final_C"] - b["final_C"]) / (abs(b["final_C"]) + 1e-12)
        max_err = max(max_err, err)
        if err > curve_rtol:
            ok = False
            msgs.append(f"FAIL {key}: final distortion diverged "
                        f"(rel err {err:.2e} > {curve_rtol:.0e})")
    _gate(gates, "hier wire-byte fields drifted", drifted, 0)
    _gate(gates, "hier final distortion max rel err", max_err, curve_rtol)

    red_ok, red_msgs = _check_reduction_record(
        baseline, fresh, kind="inter_reduction", suite="hier",
        label="inter-host sparse-vs-dense tier-1 wire reduction",
        floor=min_sparse_reduction, gates=gates)
    par_ok, par_msgs = _check_parity_record(
        baseline, fresh, kind="hier_parity", label="hier/flat wall parity",
        max_ratio_regression=max_ratio_regression, gates=gates)
    return ok and red_ok and par_ok, msgs + red_msgs + par_msgs


def _adapt_cells(doc: dict) -> dict[tuple[str, str], dict]:
    return {(r["merge"], r["quant"]): r
            for r in doc.get("results", []) if r.get("kind") == "cell"}


def check_adapt(baseline: dict, fresh: dict, *,
                curve_rtol: float = 1e-2,
                gates: list | None = None) -> tuple[bool, list[str]]:
    """Adapt-suite gate; same contract as ``check``.

    Wire bytes AND trigger counts are trace-exact on a seeded workload, so
    every cell and fixed-tau leg must match the baseline EXACTLY — drift
    means the trigger rule, the probe accounting, or a codec's wire
    formula changed.  On top of the baseline pins, the fresh summary
    record must clear the ISSUE's absolute bars: the thresh=0/quant-off
    run bit-matches the plain delta merge, and the dynamic-dense and
    dynamic-int8 cells land within ``curve_rtol`` of the best fixed-tau
    leg's final distortion at STRICTLY fewer total wire bytes.
    """
    msgs: list[str] = []
    ok = True
    b_cells, f_cells = _adapt_cells(baseline), _adapt_cells(fresh)
    missing = sorted(set(b_cells) - set(f_cells))
    if missing:
        raise ValueError(
            f"fresh adapt run is missing baseline cells {missing} — the "
            f"sweep lost coverage (regenerate the baseline only if the "
            f"cell was removed on purpose)")
    common = sorted(set(b_cells) & set(f_cells))
    if not common:
        raise ValueError("no (merge, quant) cells shared between baseline "
                         "and fresh adapt output — regenerate with "
                         "benchmarks.run --suite adapt")
    drifted = 0
    max_err = 0.0
    for key in common:
        b, f = b_cells[key], f_cells[key]
        cfg = ("m", "n", "d", "kappa", "tau", "thresh", "max_stale")
        if tuple(b.get(k) for k in cfg) != tuple(f.get(k) for k in cfg):
            raise ValueError(
                f"{key}: baseline config != fresh — regenerate the "
                f"baseline (benchmarks.run --suite adapt) instead of "
                f"comparing different runs")
        pins = ("total_wire_bytes", "merge_wire_bytes", "probe_wire_bytes",
                "n_triggered")
        bad = [p for p in pins if b[p] != f[p]]
        if bad:
            ok = False
            drifted += 1
            msgs.append(
                f"FAIL {key}: " + "; ".join(
                    f"{p} drifted {b[p]} -> {f[p]}" for p in bad))
        else:
            msgs.append(
                f"ok   {key}: wire {f['total_wire_bytes']} B, "
                f"trig {f['n_triggered']}/{f['n_windows']} (exact)")
        err = abs(f["final_C"] - b["final_C"]) / (abs(b["final_C"]) + 1e-12)
        max_err = max(max_err, err)
        if err > curve_rtol:
            ok = False
            msgs.append(f"FAIL {key}: final distortion diverged "
                        f"(rel err {err:.2e} > {curve_rtol:.0e})")
    _gate(gates, "adapt wire/trigger cells drifted", drifted, 0)
    _gate(gates, "adapt final distortion max rel err", max_err, curve_rtol)

    b_legs = {r["tau"]: r for r in baseline.get("results", [])
              if r.get("kind") == "fixed_leg"}
    f_legs = {r["tau"]: r for r in fresh.get("results", [])
              if r.get("kind") == "fixed_leg"}
    leg_drift = 0
    for tau in sorted(set(b_legs) & set(f_legs)):
        if b_legs[tau]["total_wire_bytes"] != f_legs[tau]["total_wire_bytes"]:
            ok = False
            leg_drift += 1
            msgs.append(f"FAIL fixed tau={tau}: wire drifted "
                        f"{b_legs[tau]['total_wire_bytes']} -> "
                        f"{f_legs[tau]['total_wire_bytes']}")
    _gate(gates, "adapt fixed-tau leg wire drifted", leg_drift, 0)

    s = _serve_rec(fresh, "adapt_summary")
    if s is None or _serve_rec(baseline, "adapt_summary") is None:
        return False, msgs + ["FAIL adapt suite needs an 'adapt_summary' "
                              "record in both baseline and fresh output"]
    _gate(gates, "adapt thresh=0 bitmatch", float(s["bitmatch"]), 1.0, "==")
    if not s["bitmatch"]:
        ok = False
        msgs.append("FAIL thresh=0 dynamic merge did not bit-match the "
                    "plain delta merge")
    else:
        msgs.append("ok   thresh=0 + quant-off dynamic merge bit-matches "
                    "the plain delta merge")
    _gate(gates, "adapt dynamic<=fixed wire per quant",
          float(s["dynamic_wire_ok"]), 1.0, "==")
    if not s["dynamic_wire_ok"]:
        ok = False
        msgs.append("FAIL a dynamic cell moved more total wire than its "
                    "fixed counterpart (the probe isn't paying for itself)")
    best_c, best_w = s["best_final_C"], s["best_wire_bytes"]
    for leg in ("dense", "int8"):
        c, w = s[f"dyn_{leg}_final_C"], s[f"dyn_{leg}_wire_bytes"]
        ratio = c / (best_c + 1e-12)
        _gate(gates, f"adapt dyn-{leg} C over best fixed", ratio,
              1.0 + curve_rtol)
        _gate(gates, f"adapt dyn-{leg} wire under best fixed", w,
              best_w - 1)
        if ratio > 1.0 + curve_rtol or w >= best_w:
            ok = False
            msgs.append(
                f"FAIL dynamic-{leg}: C={c:.5f} wire={w} vs best fixed "
                f"tau={s['best_tau']} C={best_c:.5f} wire={best_w} "
                f"(need C within rtol {curve_rtol:.0e} at strictly "
                f"fewer bytes)")
        else:
            msgs.append(
                f"ok   dynamic-{leg}: C {ratio:.4f}x of best fixed "
                f"(tau={s['best_tau']}) at {w}/{best_w} wire bytes")
    return ok, msgs


def check_obs(baseline: dict, fresh: dict, *, max_overhead: float = 1.03,
              gates: list | None = None) -> tuple[bool, list[str]]:
    """Obs-suite gate; same contract as ``check``.

    The overhead bar is ABSOLUTE (the acceptance criterion: live
    instrumentation costs < 3% wall), measured fresh on one box — the
    machine divides out of the on/off ratio, so the baseline pins the
    config and records the noise floor rather than anchoring a ratio.
    The trace leg is functional and machine-independent: the fresh
    traced hierarchical run must pass the invariant checker.
    """
    msgs: list[str] = []
    ok = True
    b_over = {r["scheme"]: r for r in baseline.get("results", [])
              if r.get("kind") == "overhead"}
    f_over = {r["scheme"]: r for r in fresh.get("results", [])
              if r.get("kind") == "overhead"}
    if not b_over or not f_over:
        raise ValueError("obs suite needs 'overhead' records in both "
                         "baseline and fresh output — regenerate with "
                         "benchmarks.run --suite obs")
    missing = sorted(set(b_over) - set(f_over))
    if missing:
        raise ValueError(f"fresh obs run is missing baseline overhead "
                         f"legs {missing} — the suite lost coverage")
    for scheme in sorted(f_over):
        f = f_over[scheme]
        b = b_over.get(scheme)
        if b is not None:
            cfg = ("m", "n", "d", "kappa", "tau")
            if tuple(b.get(k) for k in cfg) != tuple(f.get(k) for k in cfg):
                raise ValueError(
                    f"obs overhead [{scheme}]: baseline config != fresh — "
                    f"regenerate the baseline (benchmarks.run --suite obs) "
                    f"instead of comparing different runs")
        _gate(gates, f"obs overhead [{scheme}]", f["overhead"], max_overhead)
        line = (f"obs overhead [{scheme}]: instrumented/bare wall "
                f"{f['overhead']:.3f}x (bar <= {max_overhead:.2f}x)")
        if f["overhead"] > max_overhead:
            ok = False
            msgs.append(f"FAIL {line}")
        else:
            msgs.append(f"ok   {line}")

    tr = _serve_rec(fresh, "trace")
    _gate(gates, "obs trace invariants ok",
          1 if (tr and tr.get("trace_ok", False)) else 0, 1, ">=")
    if tr is None:
        ok = False
        msgs.append("FAIL fresh obs run has no 'trace' record")
    elif not tr.get("trace_ok", False):
        ok = False
        msgs.append("FAIL traced hierarchical run violated trace "
                    "invariants: "
                    + "; ".join(tr.get("errors", ["(no detail)"])[:3]))
    else:
        msgs.append(f"ok   traced {tr.get('hosts')}-host run: "
                    f"{tr.get('n_spans')} spans, tier-0/1 merge spans + "
                    f"divergence counter present")
    return ok, msgs


def check_chaos(baseline: dict | None, fresh: dict, *,
                max_chaos_distortion: float = 1.25,
                curve_rtol: float = 1e-2,
                gates: list | None = None) -> tuple[bool, list[str]]:
    """Chaos-suite gate; same contract as ``check``.

    ``baseline=None`` is the ``--absolute`` mode used by the cron seed
    sweep: only the absolute bars apply (distortion-ratio ceiling over
    the fault-free oracle + trace invariants) since sweep seeds have no
    committed baseline to diff against.
    """
    msgs: list[str] = []
    ok = True

    f = _serve_rec(fresh, "chaos")
    if f is None:
        raise ValueError("chaos suite needs a 'chaos' record in the fresh "
                         "output — regenerate with "
                         "benchmarks.run --suite chaos")

    if baseline is not None:
        b = _serve_rec(baseline, "chaos")
        if b is None:
            raise ValueError("chaos baseline has no 'chaos' record — "
                             "regenerate with benchmarks.run --suite chaos")
        cfg = ("seed", "m", "n", "d", "kappa", "tau", "hosts", "quorum_frac")
        b_cfg = tuple(b.get(k) for k in cfg)
        f_cfg = tuple(f.get(k) for k in cfg)
        if b_cfg != f_cfg:
            raise ValueError(
                f"chaos config mismatch baseline={b_cfg} fresh={f_cfg} — "
                f"regenerate the baseline (benchmarks.run --suite chaos) "
                f"instead of comparing different runs")
        # determinism pin: the same seed must draw the identical
        # kill/slow/partition schedule on every device count
        if b.get("events") != f.get("events"):
            ok = False
            msgs.append("FAIL chaos schedule drifted from baseline — same "
                        "seed must draw identical events "
                        f"(baseline {b.get('events')} != "
                        f"fresh {f.get('events')})")
        else:
            msgs.append(f"ok   seeded schedule: {len(f.get('events', []))} "
                        f"events, identical to baseline")
        wire = (b.get("merge_wire_bytes"), f.get("merge_wire_bytes"))
        if wire[0] != wire[1]:
            ok = False
            msgs.append(f"FAIL quorum-merge wire bytes drifted "
                        f"{wire[0]} -> {wire[1]} B (masked-collective "
                        f"accounting or structure changed)")
        else:
            msgs.append(f"ok   quorum merge wire {wire[1]} B (exact)")
        err = (abs(f["final_C"] - b["final_C"])
               / (abs(b["final_C"]) + 1e-12))
        if err > curve_rtol:
            ok = False
            msgs.append(f"FAIL chaos final distortion diverged from "
                        f"baseline: rel err {err:.2e} > {curve_rtol:.0e}")
        else:
            msgs.append(f"ok   chaos final distortion rel err {err:.2e}")

    _gate(gates, "chaos distortion ratio vs oracle", f["distortion_ratio"],
          max_chaos_distortion)
    _gate(gates, "chaos trace invariants ok",
          1 if f.get("trace_ok", False) else 0, 1, ">=")
    line = (f"distortion ratio vs fault-free oracle "
            f"{f['distortion_ratio']:.4f} "
            f"(bound {max_chaos_distortion:.2f}, "
            f"{len(f.get('resizes', []))} unscheduled resizes survived)")
    if f["distortion_ratio"] > max_chaos_distortion:
        ok = False
        msgs.append(f"FAIL {line}")
    else:
        msgs.append(f"ok   {line}")

    if not f.get("trace_ok", False):
        ok = False
        msgs.append("FAIL chaos trace violated invariants: "
                    + "; ".join(f.get("trace_errors", ["(no detail)"])[:3]))
    else:
        msgs.append("ok   chaos trace: chaos_* spans present, "
                    "invariants hold")
    return ok, msgs


def attribution_deltas(baseline: dict, fresh: dict) -> list[str]:
    """Per-scheme roofline-term movement between two profile docs.

    This is what turns a wall regression from "slower" into "slower
    BECAUSE": printed on every profile-gate run and, by ``main``, as a
    diagnostic whenever ANY suite's gate fails and a fresh profile doc is
    available next to the committed one."""
    b_idx = {r["scheme"]: r for r in baseline.get("results", [])
             if r.get("kind") == "attribution"}
    f_idx = {r["scheme"]: r for r in fresh.get("results", [])
             if r.get("kind") == "attribution"}
    out: list[str] = []
    for scheme in sorted(set(b_idx) & set(f_idx)):
        ba, fa = b_idx[scheme]["attribution"], f_idx[scheme]["attribution"]
        moved = []
        for term in ("compute", "memory", "collective", "host"):
            bt, ft = ba.get(f"t_{term}_s", 0.0), fa.get(f"t_{term}_s", 0.0)
            if bt > 0:
                moved.append(f"{term} {bt * 1e6:.2f}->{ft * 1e6:.2f}us "
                             f"({ft / bt:.2f}x)")
            elif ft > 0:
                moved.append(f"{term} 0->{ft * 1e6:.2f}us (new)")
        wall = (f"window wall {ba['window_wall_s'] * 1e6:.1f}->"
                f"{fa['window_wall_s'] * 1e6:.1f}us")
        out.append(f"attribution [{scheme}]: {wall}; " + ", ".join(moved))
    return out


def check_profile(baseline: dict, fresh: dict, *,
                  max_consistency: float = 0.15,
                  min_compute_eff: float = 1e-9,
                  gates: list | None = None) -> tuple[bool, list[str]]:
    """Profile-suite gate; same contract as ``check``.

    * attribution-sum consistency: the roofline terms (host residual
      included) must sum to the measured window wall within
      ``max_consistency`` (0.15 — the acceptance criterion).  The
      residual is clamped at zero, so a violation means the ANALYTIC
      terms overshoot measured wall: a wrong flop/byte count or a
      mis-inferred while-loop trip count;
    * compute-term efficiency floor: the analytic compute term must be
      present and positive (TPU-peak-relative, so tiny on the CPU
      harness — the floor pins attribution happened, not CPU speed);
    * ``collective_bytes_per_window`` is trip-count-corrected HLO shape
      arithmetic — machine-independent, pinned EXACTLY against the
      baseline, and cross-checked against the transport's own CommLog
      logical-byte accounting of the same program.

    On any failure the per-term attribution deltas vs the baseline are
    appended, so the log says WHICH roofline term moved, not just that
    wall did.
    """
    msgs: list[str] = []
    ok = True
    b_idx = {r["scheme"]: r for r in baseline.get("results", [])
             if r.get("kind") == "attribution"}
    f_idx = {r["scheme"]: r for r in fresh.get("results", [])
             if r.get("kind") == "attribution"}
    if not b_idx or not f_idx:
        raise ValueError("profile suite needs 'attribution' records in both "
                         "baseline and fresh output — regenerate with "
                         "benchmarks.run --suite profile")
    missing = sorted(set(b_idx) - set(f_idx))
    if missing:
        raise ValueError(f"fresh profile run is missing baseline schemes "
                         f"{missing} — the suite lost coverage")
    worst_cons = 0.0
    min_eff = float("inf")
    for scheme in sorted(f_idx):
        f = f_idx[scheme]
        b = b_idx.get(scheme)
        fa = f["attribution"]
        if b is not None:
            cfg = ("m", "n", "d", "kappa", "tau", "transport")
            if tuple(b.get(k) for k in cfg) != tuple(f.get(k) for k in cfg):
                raise ValueError(
                    f"profile [{scheme}]: baseline config != fresh — "
                    f"regenerate the baseline (benchmarks.run --suite "
                    f"profile) instead of comparing different runs")
        cons = fa["consistency"]
        worst_cons = max(worst_cons, cons)
        line = (f"profile [{scheme}]: attribution sum vs measured window "
                f"wall off by {cons:.4f} (bar <= {max_consistency:.2f})")
        if cons > max_consistency:
            ok = False
            msgs.append(f"FAIL {line} — modeled terms overshoot measured "
                        f"wall (bad analytic count or trip count)")
        else:
            msgs.append(f"ok   {line}")
        eff = fa["efficiency"].get("compute", 0.0)
        min_eff = min(min_eff, eff)
        if eff < min_compute_eff:
            ok = False
            msgs.append(f"FAIL profile [{scheme}]: compute-term efficiency "
                        f"{eff:.3e} below the {min_compute_eff:.0e} floor "
                        f"(attribution lost the analytic compute term)")
        if b is not None:
            bw = b["attribution"]["collective_bytes_per_window"]
            fw = fa["collective_bytes_per_window"]
            if bw != fw:
                ok = False
                msgs.append(
                    f"FAIL profile [{scheme}]: HLO collective bytes/window "
                    f"drifted {bw} -> {fw} (collective structure or trip-"
                    f"count inference changed)")
            else:
                msgs.append(f"ok   profile [{scheme}]: collective "
                            f"{fw:.0f} B/window (HLO, exact)")
        log_pw = f.get("commlog_logical_bytes_per_window")
        if log_pw:
            rel = abs(fa["collective_bytes_per_window"] - log_pw) / log_pw
            if rel > 1e-6:
                ok = False
                msgs.append(
                    f"FAIL profile [{scheme}]: HLO bytes/window "
                    f"{fa['collective_bytes_per_window']:.1f} != CommLog "
                    f"{log_pw:.1f} (rel {rel:.2e}) — the parsed program "
                    f"disagrees with the transport's own accounting")
            else:
                msgs.append(f"ok   profile [{scheme}]: HLO == CommLog "
                            f"logical bytes ({log_pw:.1f} B/window)")
    _gate(gates, "profile attribution consistency (worst)", worst_cons,
          max_consistency)
    _gate(gates, "profile compute efficiency (min)", min_eff,
          min_compute_eff, ">=")
    if not ok:
        msgs += attribution_deltas(baseline, fresh)
    return ok, msgs


def _sample_tag(rec: dict) -> str:
    """Short human tag for a BENCH record carrying raw samples."""
    for keys in (("executor", "m"), ("kind", "scheme"),
                 ("scheme", "transport"), ("scheme", "variant"),
                 ("variant",), ("kind",)):
        if all(rec.get(k) is not None for k in keys):
            return "/".join(str(rec[k]) for k in keys)
    return "record"


def variance_warnings(doc: dict, *, threshold: float,
                      label: str = "baseline") -> list[str]:
    """WARN when recorded per-iteration wall samples spread wider than the
    regression threshold — a ratio FAIL against such a baseline is as
    likely noise as regression (regenerate the baseline on a quieter box
    rather than widening the gate).  Never fails the run."""
    warns: list[str] = []
    for rec in doc.get("results", []):
        for fld in ("wall_samples", "wall_samples_off", "wall_samples_on"):
            s = rec.get(fld)
            if not isinstance(s, list) or len(s) < 2 or min(s) <= 0:
                continue
            spread = max(s) / min(s) - 1.0
            if spread > threshold:
                warns.append(
                    f"warn {label} {_sample_tag(rec)}: {fld} spread "
                    f"{spread:.0%} exceeds the {threshold:.0%} regression "
                    f"threshold — wall-ratio gates on this record are "
                    f"noise-limited")
    return warns


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline", default="BENCH_engine.json")
    ap.add_argument("--fresh", default="BENCH_engine.fresh.json")
    ap.add_argument("--max-ratio-regression", type=float, default=1.25,
                    help="allowed mesh/sim wall-ratio (engine) or batching-"
                         "speedup (serve) regression (1.25 = +25%%)")
    ap.add_argument("--curve-rtol", type=float, default=1e-2)
    ap.add_argument("--min-speedup", type=float, default=4.0,
                    help="serve suite: absolute floor for the batched-over-"
                         "unbatched lookup speedup")
    ap.add_argument("--min-sparse-reduction", type=float, default=4.0,
                    help="comm suite: floor for the sparse-vs-dense merge "
                         "wire-byte reduction (4x at k/kappa = 0.25)")
    ap.add_argument("--max-obs-overhead", type=float, default=1.03,
                    help="obs suite: absolute ceiling for the live-"
                         "instrumentation wall overhead (1.03 = the <3%% "
                         "acceptance bar)")
    ap.add_argument("--max-chaos-distortion", type=float, default=1.25,
                    help="chaos suite: absolute ceiling for the faulted "
                         "run's final distortion over the fault-free "
                         "oracle (1.25 = within 25%%)")
    ap.add_argument("--max-consistency", type=float, default=0.15,
                    help="profile suite: ceiling for |attributed - "
                         "measured| / measured on the per-window wall "
                         "(0.15 = the 15%% acceptance bar)")
    ap.add_argument("--min-compute-eff", type=float, default=1e-9,
                    help="profile suite: floor for the compute-term "
                         "roofline efficiency (TPU-peak-relative, so "
                         "tiny on the CPU CI harness; the floor proves "
                         "attribution ran, it does not rate hardware)")
    ap.add_argument("--absolute", action="store_true",
                    help="chaos suite: gate the fresh output on the "
                         "absolute bars alone, no baseline file (the "
                         "cron seed sweep runs seeds with no committed "
                         "baseline)")
    args = ap.parse_args(argv)
    try:
        with open(args.fresh) as fh:
            fresh = json.load(fh)
        baseline = None
        if not args.absolute:
            with open(args.baseline) as fh:
                baseline = json.load(fh)
    except (OSError, json.JSONDecodeError) as e:
        # exit 3, distinct from config-mismatch (2) and regression (1):
        # a missing/truncated benchmark file is a SETUP failure and CI
        # must not report it as either a perf regression or a pass
        print(f"error: missing or unreadable benchmark file: {e}",
              file=sys.stderr)
        return 3
    if args.absolute:
        suite = fresh.get("suite", "engine")
        if suite != "chaos":
            print(f"error: --absolute only applies to the chaos suite, "
                  f"fresh is {suite!r}", file=sys.stderr)
            return 2
        suites = ("chaos", "chaos")
    else:
        suites = (baseline.get("suite", "engine"),
                  fresh.get("suite", "engine"))
        if suites[0] != suites[1]:
            print(f"error: baseline suite {suites[0]!r} != fresh "
                  f"{suites[1]!r}", file=sys.stderr)
            return 2
    gates: list[dict] = []
    try:
        if suites[0] == "serve":
            ok, msgs = check_serve(
                baseline, fresh,
                max_ratio_regression=args.max_ratio_regression,
                min_speedup=args.min_speedup, gates=gates)
        elif suites[0] == "comm":
            ok, msgs = check_comm(
                baseline, fresh,
                max_ratio_regression=args.max_ratio_regression,
                min_sparse_reduction=args.min_sparse_reduction,
                curve_rtol=args.curve_rtol, gates=gates)
        elif suites[0] == "hier":
            ok, msgs = check_hier(
                baseline, fresh,
                max_ratio_regression=args.max_ratio_regression,
                min_sparse_reduction=args.min_sparse_reduction,
                curve_rtol=args.curve_rtol, gates=gates)
        elif suites[0] == "obs":
            ok, msgs = check_obs(baseline, fresh,
                                 max_overhead=args.max_obs_overhead,
                                 gates=gates)
        elif suites[0] == "chaos":
            ok, msgs = check_chaos(
                baseline, fresh,
                max_chaos_distortion=args.max_chaos_distortion,
                curve_rtol=args.curve_rtol, gates=gates)
        elif suites[0] == "profile":
            ok, msgs = check_profile(
                baseline, fresh,
                max_consistency=args.max_consistency,
                min_compute_eff=args.min_compute_eff, gates=gates)
        elif suites[0] == "adapt":
            ok, msgs = check_adapt(baseline, fresh,
                                   curve_rtol=args.curve_rtol, gates=gates)
        else:
            ok, msgs = check(baseline, fresh,
                             max_ratio_regression=args.max_ratio_regression,
                             curve_rtol=args.curve_rtol, gates=gates)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    thresh = (args.max_obs_overhead - 1.0 if suites[0] == "obs"
              else args.max_ratio_regression - 1.0)
    if baseline is not None:
        msgs += variance_warnings(baseline, threshold=thresh)
    if not ok and suites[0] != "profile":
        # any suite's wall gate failing: attribute the regression if a
        # fresh profile run sits next to the committed baseline — say
        # WHICH roofline term moved, not just that wall did
        msgs += _profile_attribution_diag(args.fresh)
    for m in msgs:
        print(m)
    if gates:
        print()
        print(gate_table(gates))
    print("benchmark regression gate:", "PASS" if ok else "FAIL")
    return 0 if ok else 1


def _profile_attribution_diag(fresh_path: str) -> list[str]:
    """Best-effort roofline attribution of a non-profile gate failure.

    Looks for ``BENCH_profile.json`` (committed) and
    ``BENCH_profile.fresh.json`` beside the failing suite's fresh file;
    silent if either is absent — this is a diagnostic, never a gate."""
    d = os.path.dirname(os.path.abspath(fresh_path))
    try:
        with open(os.path.join(d, "BENCH_profile.json")) as fh:
            base = json.load(fh)
        with open(os.path.join(d, "BENCH_profile.fresh.json")) as fh:
            fresh = json.load(fh)
    except (OSError, json.JSONDecodeError):
        return []
    deltas = attribution_deltas(base, fresh)
    if deltas:
        deltas.insert(0, "roofline attribution of the regression "
                         "(BENCH_profile.fresh.json vs committed):")
    return deltas


if __name__ == "__main__":
    raise SystemExit(main())
