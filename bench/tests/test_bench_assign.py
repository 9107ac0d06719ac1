"""The per-step assign kernel's work count and its roofline reader, at the
``sift1m-ivf4096`` shape, and the cells the manifest reads it in."""

import json

import pytest

import tiny_cells
from test_bench_work import _config, _metric, _run, _work

BENCH = tiny_cells.BENCH
harness = tiny_cells.harness


def test_per_step_assign_counts_at_kappa_4096():
    c = _config("sift1m-ivf4096")
    w = _work("vq_assign")
    shape = (c["kappa"], c["d"])
    # one (1, 128) x (128, 4096) product a point
    assert w.flops_per_point(*shape) == 2 * 4096 * 128 == 1_048_576
    # the point in; the codebook and its norms stay in on-core memory
    assert w.bytes_per_point(*shape) == 128 * 4


def test_assign_roofline_reads_least_time_over_kernel_time():
    c = _config("sift1m-ivf4096")
    # 50,000 points, each bound by 1.05 MFLOP at 197 TFLOP/s
    least = 50_000 * 1_048_576 / 197e12
    run = _run(c, {"vq_assign": 4 * least, "vq_delta": least}, 50_000)
    assert _metric("vq_assign_roofline").read(run) == pytest.approx(25.0)


def test_assign_roofline_reads_nothing_from_a_delta_step():
    """The per-step route before it ran ``vq_assign``: traced points and
    ``vq_delta`` time, but no ``vq_assign`` events."""
    run = _run(_config("sift1m-ivf4096"), {"vq_delta": 0.5}, 50_000)
    assert _metric("vq_assign_roofline").read(run) is None


def test_assign_roofline_reads_nothing_without_traced_points():
    run = _run(_config("sift1m-ivf1024"), {}, 0)
    assert _metric("vq_assign_roofline").read(run) is None


def test_assign_roofline_is_read_in_the_per_step_cell_alone():
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == "vq_assign_roofline"]
    assert entry["workloads"] == ["sift1m-ivf4096.train"]
    assert entry["layer"] == "per-step kernel (kernels/vq_assign.py)"
    for w in manifest["workloads"]:
        cell = harness.find_cell(w["name"], manifest)
        names = {m["name"] for m in cell.per_layer}
        assert ("vq_assign_roofline" in names) == (
            w["name"] == "sift1m-ivf4096.train")
