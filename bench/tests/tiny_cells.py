"""The benchmark's cells cut to a size a CPU test run can hold.

The widths of the serving cell stay (d=128, so the bf16 control's error
has the scale it has on the chip); the stream, the codebooks and the
chunks shrink.  The limits are the cell's own, from ``limits/``.
"""

from __future__ import annotations

import dataclasses
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
if str(BENCH.parent / "src") not in sys.path:
    sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402

TRAIN = {"d": 16, "n_eval": 512, "n_centers": 64}
SERVE = {"n_points": 8192, "n_centers": 64, "kappa": 256}


def cell(name: str, chunk_points: int = 200,
         traffic: str | None = None) -> harness.Cell:
    """Cell ``name`` of ``BENCHMARK.json`` at a CPU test's size; a
    training cell's stream holds eight chunks of ``chunk_points`` points
    per worker.  ``traffic`` puts another mix of ``traffic/`` in the
    cell's place, with one chip per worker."""
    full = harness.find_cell(name)
    if traffic is not None:
        mix = harness.load_json(BENCH / "traffic" / f"{traffic}.json")
        entry = dict(full.entry, traffic=traffic,
                     chips=mix.get("workers", full.entry["chips"]))
        full = dataclasses.replace(full, entry=entry, traffic=mix)
    if full.kind == "train_stream":
        n = 8 * chunk_points * full.traffic["workers"]
        config = dict(full.config, **TRAIN, n_points=n,
                      kappa=full.config["kappa"] // 32)
        traffic = dict(full.traffic, chunk_points=chunk_points)
    else:
        config = dict(full.config, **SERVE)
        traffic = dict(full.traffic, rate_rps=300, pool=2048,
                       warm_requests=40, collect_timeout_s=10)
    return dataclasses.replace(full, config=config, traffic=traffic)


def run(name: str, seconds: float = 0.5, chunk_points: int = 200,
        traffic: str | None = None) -> dict:
    """One run of the cut cell on the CPU, the chip look skipped."""
    c = cell(name, chunk_points, traffic)
    return harness.run_cell(name, seed=2**33 + 7, seconds=seconds,
                            trace=False, t_start=0.0, require_tpu=False,
                            cell=c)


def no_cache(monkeypatch) -> None:
    """Keep the test process's compile cache as it is."""
    from repro import compile_cache

    monkeypatch.setattr(compile_cache, "enable", lambda: os.environ.get(
        compile_cache.ENV, ""))
