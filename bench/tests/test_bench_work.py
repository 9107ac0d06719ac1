"""The work counts behind the roofline and mfu metrics, against hand
values at the two configurations' shapes, and the peak table."""

import json
from types import SimpleNamespace

import pytest

import tiny_cells

BENCH = tiny_cells.BENCH
harness = tiny_cells.harness


def _work(layer):
    return harness.load_module(BENCH / "work" / f"{layer}.py",
                               f"test_work_{layer}")


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_window_counts_at_kappa_1024():
    c = _config("sift1m-ivf1024")
    w = _work("vq_window")
    shape = (c["kappa"], c["d"], c["tau"])
    assert shape == (1024, 128, 10)
    # ten (1, 128) x (128, 1024) products
    assert w.flops_per_window(*shape) == 2 * 10 * 1024 * 128 == 2_621_440
    # the ten points in; the codebook stays in on-core memory
    assert w.bytes_per_window(*shape) == 10 * 128 * 4


def test_per_step_counts_at_kappa_4096():
    c = _config("sift1m-ivf4096")
    w = _work("vq_delta")
    shape = (c["kappa"], c["d"])
    assert shape == (4096, 128)
    assert w.flops_per_point(*shape) == 1_048_576
    # the point in; the codebook stays in on-core memory
    assert w.bytes_per_point(*shape) == 128 * 4


@pytest.mark.parametrize("name, flops", [("sift1m-ivf1024", 262_144),
                                         ("sift1m-ivf4096", 1_048_576)])
def test_nearest_search_counts(name, flops):
    c = _config(name)
    assert _work("nearest_search").flops_per_point(c["kappa"], c["d"]) == flops


def test_peaks_of_a_v5e():
    import peaks

    p = peaks.peaks("TPU v5 lite")
    assert p == {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5 lite "])
def test_unknown_device_kind_raises(kind):
    import peaks

    with pytest.raises(KeyError):
        peaks.peaks(kind)


class _Summary:
    def __init__(self, kernel_s, window_s=1.0, idle=0.25):
        self._kernel_s, self.window_s = kernel_s, window_s
        self._idle = idle

    def kernel_s(self, name):
        return self._kernel_s.get(name, 0.0)

    def idle_share(self, device=0):
        return self._idle


def _run(config, kernel_s, points, chips=1, **kw):
    import peaks

    return SimpleNamespace(
        summary=_Summary(kernel_s, **kw),
        counters={"traced_points_per_worker": points}, config=config,
        chips=chips, peaks=peaks.peaks("TPU v5 lite"), load_work=_work)


def _metric(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py",
                               f"test_metric_{name}")


def test_window_roofline_reads_least_time_over_kernel_time():
    c = _config("sift1m-ivf1024")
    # 50,000 points = 5,000 windows, each bound by 2.6 MFLOP at 197 TFLOP/s
    least = 5000 * 2_621_440 / 197e12
    run = _run(c, {"vq_window": 4 * least}, 50_000)
    assert _metric("vq_window_roofline").read(run) == pytest.approx(25.0)


def test_delta_roofline_reads_least_time_over_kernel_time():
    c = _config("sift1m-ivf4096")
    least = 50_000 * 1_048_576 / 197e12
    run = _run(c, {"vq_delta": 2 * least}, 50_000)
    assert _metric("vq_delta_roofline").read(run) == pytest.approx(50.0)


def test_train_mfu_counts_the_search_alone():
    c = _config("sift1m-ivf1024")
    run = _run(c, {}, 50_000, chips=4, window_s=0.5)
    want = 100 * 4 * 50_000 * 262_144 / (0.5 * 4 * 197e12)
    assert _metric("train_mfu").read(run) == pytest.approx(want)


@pytest.mark.parametrize("name", ["vq_window_roofline", "vq_delta_roofline",
                                  "train_mfu"])
def test_readers_with_nothing_to_read_return_none(name):
    run = _run(_config("sift1m-ivf1024"), {}, 0)
    assert _metric(name).read(run) is None
