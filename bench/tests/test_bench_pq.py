"""The product-quantizer cell and the one-row serving mix, cut to a CPU
test's size: a sound run is correct, the control and a planted fault are
not, and the PQ kernel's work and roofline read what they should.  The
one-row mix (``traffic/serve-1row.json``) has no cell of its own yet; it
runs in the serve cell's place, with that cell's limits."""

import dataclasses
import json
from types import SimpleNamespace

import numpy as np
import pytest

import tiny_cells

BENCH = tiny_cells.BENCH
harness = tiny_cells.harness
PQ = "sift1m-pq16x256.train"
SERVE = "sift1m-ivf4096.serve"


@pytest.fixture(autouse=True)
def _no_cache(monkeypatch):
    tiny_cells.no_cache(monkeypatch)


def pq_cell(chunk_points: int = 2000) -> harness.Cell:
    """The PQ cell at d=32 (m=4 sub-spaces of 8, as the cell's), k*=16 and
    eight chunks of ``chunk_points`` points.  (In bfloat16 a codebook stops
    moving once the step size falls under its rounding, so the control
    needs some thousand points a chunk to show.)"""
    full = harness.find_cell(PQ)
    config = dict(full.config, d=32, m=4, kappa=16, n_eval=512,
                  n_centers=64, n_points=8 * chunk_points)
    traffic = dict(full.traffic, chunk_points=chunk_points)
    return dataclasses.replace(full, config=config, traffic=traffic)


def _check(variant):
    cell = pq_cell()
    devices = harness.prepare(cell, require_tpu=False)
    driver = harness.make_driver(cell, 2**33 + 9, devices, variant)
    driver.setup()
    return harness.judge(driver.check(), cell.limits)


def test_pq_sound_run_is_correct():
    res = harness.run_cell(PQ, seed=2**33 + 7, seconds=0.5, trace=False,
                           t_start=0.0, require_tpu=False, cell=pq_cell())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["train_points_per_s"]["value"] > 0


@pytest.mark.parametrize("variant", ["control", "half"])
def test_pq_control_and_fault_fail(variant):
    correct, compared = _check(variant)
    assert not correct, compared


def test_pq_state_left_unchanged_fails(monkeypatch):
    from repro.engine.mesh import MeshExecutor

    orig = MeshExecutor.run_segment

    def run_segment(self, scheme, w0, data, ev, **kw):
        return orig(self, scheme, w0, data, ev, **kw)._replace(w_shared=w0)

    monkeypatch.setattr(MeshExecutor, "run_segment", run_segment)
    res = harness.run_cell(PQ, seed=2**33 + 7, seconds=0.5, trace=False,
                           t_start=0.0, require_tpu=False, cell=pq_cell(200))
    assert not res["correct"]
    assert res["checks"]["step1_gap"]["value"] == pytest.approx(1.0)


def _load(kind, name):
    return harness.load_module(BENCH / kind / f"{name}.py",
                               f"test_pq_{kind}_{name}")


def test_pq_window_counts_at_the_cell_shape():
    c = json.loads((BENCH / "configs" / "sift1m-pq16x256.json").read_text())
    w = _load("work", "pq_window")
    shape = (c["kappa"], c["d"], c["tau"])
    assert shape == (256, 128, 10) and c["m"] == 16
    # ten steps, each 16 sub-spaces x 256 codes x 8 coordinates x 2
    assert (w.flops_per_window(*shape) == 10 * 16 * 256 * 8 * 2
            == 655_360)
    # the ten points in; the 128 KiB of sub-codebooks stay on the core
    assert w.bytes_per_window(*shape) == 10 * 128 * 4


class _Summary:
    def __init__(self, kernel_s):
        self._kernel_s = kernel_s

    def kernel_s(self, name):
        return self._kernel_s.get(name, 0.0)


def _run(kernel_s, points):
    import peaks

    c = json.loads((BENCH / "configs" / "sift1m-pq16x256.json").read_text())
    return SimpleNamespace(
        summary=_Summary(kernel_s), config=c, chips=1,
        counters={"traced_points_per_worker": points},
        peaks=peaks.peaks("TPU v5 lite"),
        load_work=lambda layer: _load("work", layer))


def test_pq_roofline_reads_least_time_over_kernel_time():
    # 50,000 points = 5,000 windows, each bound by its 5,120 bytes at 819
    # GB/s (its 655,360 FLOP at 197 TFLOP/s take less)
    least = 5000 * 5120 / 819e9
    assert 655_360 / 197e12 < 5120 / 819e9
    run = _run({"pq_window": 5 * least}, 50_000)
    assert _load("metrics", "pq_window_roofline").read(run) == \
        pytest.approx(20.0)


@pytest.mark.parametrize("kernel_s, points", [({}, 50_000),
                                              ({"vq_window": 1.0}, 50_000),
                                              ({"pq_window": 1.0}, 0)])
def test_pq_roofline_with_nothing_to_read_is_none(kernel_s, points):
    assert _load("metrics", "pq_window_roofline").read(
        _run(kernel_s, points)) is None


def test_one_row_serve_sound_run_is_correct():
    res = tiny_cells.run(SERVE, seconds=1.0, traffic="serve-1row")
    assert res["correct"], res["checks"]
    assert res["attempted"] == 300      # one row each, at the cut rate
    assert res["attempted"] > 0 and res["failed"] == 0


def test_one_row_serve_one_wrong_code_fails(monkeypatch):
    from repro.serve import ShardedLookup

    orig = ShardedLookup.assign
    calls = []

    def assign(self, z, w):
        codes, dist = orig(self, z, w)
        calls.append(1)
        if len(calls) == 120:   # past the 40 warm requests: in the window
            codes = np.asarray(codes).copy()
            codes[0] = (codes[0] + 1) % w.shape[0]
        return codes, dist

    monkeypatch.setattr(ShardedLookup, "assign", assign)
    res = tiny_cells.run(SERVE, seconds=1.0, traffic="serve-1row")
    assert not res["correct"]
    assert res["checks"]["far_rows"] == {"value": 1.0, "limit": 0}
