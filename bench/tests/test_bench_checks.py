"""The check that decides ``correct`` fails what it has to fail.

Each test drives a whole run of a cell, cut to a CPU test's size and with
the chip look skipped, through the harness, with one fault planted in the
timed path underneath, and sees ``correct`` come out false.  The sound
run and the control (the reference one precision lower, in the program's
place) are here too.  The limits are the cells' own.
"""

import numpy as np
import pytest

import tiny_cells

TRAIN = "sift1m-ivf1024.train"
TRAIN_STEP = "sift1m-ivf4096.train"
SERVE = "sift1m-ivf4096.serve"
# (cell, traffic in its place): the window route, the per-step route, and
# four workers merged over four (host) devices
FOUR = pytest.mark.devices(4)
TRAINING = [pytest.param(TRAIN, None, id="window"),
            pytest.param(TRAIN_STEP, None, id="per-step"),
            pytest.param(TRAIN, "train-4chip", id="four-workers", marks=FOUR)]


@pytest.fixture(autouse=True)
def _no_cache(monkeypatch):
    tiny_cells.no_cache(monkeypatch)


@pytest.mark.parametrize(
    "name, traffic", TRAINING + [pytest.param(SERVE, None, id="serve")])
def test_sound_run_is_correct(name, traffic):
    res = tiny_cells.run(name, traffic=traffic)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def _patch_segment(monkeypatch, wrap):
    from repro.engine.mesh import MeshExecutor

    orig = MeshExecutor.run_segment

    def run_segment(self, scheme, w0, data, eval_data, **kw):
        return wrap(orig, self, scheme, w0, data, eval_data, **kw)

    monkeypatch.setattr(MeshExecutor, "run_segment", run_segment)


@pytest.mark.parametrize("name, traffic", TRAINING)
def test_state_left_unchanged_fails(monkeypatch, name, traffic):
    def wrap(orig, self, scheme, w0, data, ev, **kw):
        return orig(self, scheme, w0, data, ev, **kw)._replace(w_shared=w0)

    _patch_segment(monkeypatch, wrap)
    res = tiny_cells.run(name, traffic=traffic)
    assert not res["correct"]
    assert res["checks"]["step1_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("name, traffic", TRAINING)
def test_half_of_each_chunk_left_out_fails(monkeypatch, name, traffic):
    def wrap(orig, self, scheme, w0, data, ev, **kw):
        return orig(self, scheme, w0, data[:, : data.shape[1] // 2], ev, **kw)

    _patch_segment(monkeypatch, wrap)
    assert not tiny_cells.run(name, chunk_points=2000,
                              traffic=traffic)["correct"]


@FOUR
def test_exchange_between_chips_left_out_fails(monkeypatch):
    import jax.numpy as jnp

    from repro.comm.xla import XlaTransport

    monkeypatch.setattr(XlaTransport, "_sum_leaf",
                        lambda self, x, axis: x.astype(jnp.float32))
    assert not tiny_cells.run(TRAIN, traffic="train-4chip")["correct"]


def _patch_lookup(monkeypatch, wrap):
    from repro.serve import ShardedLookup

    orig = ShardedLookup.assign
    monkeypatch.setattr(ShardedLookup, "assign",
                        lambda self, z, w: wrap(orig, self, z, w))


def test_served_code_altered_fails(monkeypatch):
    def wrap(orig, self, z, w):
        codes, dist = orig(self, z, w)
        codes = np.asarray(codes).copy()
        codes[0] = (codes[0] + 1) % w.shape[0]
        return codes, dist

    _patch_lookup(monkeypatch, wrap)
    assert not tiny_cells.run(SERVE, seconds=1.0)["correct"]


def test_one_wrong_code_in_the_window_fails(monkeypatch):
    """One row of one flush served the next code: the count of wrong codes
    catches it, where a mean over a chip window's rows would not."""
    calls = []

    def wrap(orig, self, z, w):
        codes, dist = orig(self, z, w)
        calls.append(1)
        if len(calls) == 120:
            codes = np.asarray(codes).copy()
            codes[0] = (codes[0] + 1) % w.shape[0]
        return codes, dist

    _patch_lookup(monkeypatch, wrap)
    res = tiny_cells.run(SERVE, seconds=1.0)
    assert not res["correct"]
    assert res["checks"]["far_rows"] == {"value": 1.0, "limit": 0}


def test_half_of_each_batch_left_out_fails(monkeypatch):
    def wrap(orig, self, z, w):
        codes, dist = orig(self, z, w)
        real = int(np.any(np.asarray(z) != 0, axis=1).sum())  # not padding
        codes = np.asarray(codes).copy()
        dist = np.asarray(dist).copy()
        codes[real // 2:real], dist[real // 2:real] = 0, 0.0
        return codes, dist

    _patch_lookup(monkeypatch, wrap)
    assert not tiny_cells.run(SERVE, seconds=1.0)["correct"]


def test_unanswered_request_fails(monkeypatch):
    calls = []

    def wrap(orig, self, z, w):
        calls.append(1)
        if len(calls) == 100:
            raise RuntimeError("planted: one flush fails")
        return orig(self, z, w)

    _patch_lookup(monkeypatch, wrap)
    assert not tiny_cells.run(SERVE, seconds=1.0)["correct"]


@pytest.mark.parametrize("name", [TRAIN, TRAIN_STEP, SERVE])
def test_control_fails(name):
    """The reference in bfloat16, in the program's place, is not correct.
    (A training cell's codebook stops moving in bfloat16 once the step
    size falls under its rounding, so the stream is some thousand points
    per chunk.)"""
    import harness

    cell = tiny_cells.cell(name, chunk_points=2000)
    devices = harness.prepare(cell, require_tpu=False)
    driver = harness.make_driver(cell, 2**33 + 9, devices, "control")
    driver.setup()
    if cell.kind != "train_stream":
        driver.window(1.0, None)
    correct, compared = harness.judge(driver.check(), cell.limits)
    assert not correct, compared
