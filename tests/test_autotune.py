"""Autotuner determinism + fused-kernel parity on ragged shapes.

Three contracts pinned here:

  * every kernel route (full-codebook, fused blocked, autotuned default)
    matches the pure-jnp oracle on shapes that do NOT divide the tiles —
    batch not a multiple of bm, kappa not a multiple of bk, kappa < bk,
    batch < 8;
  * the tuner is deterministic: same shape => same config, a cache hit
    never re-searches, and the JSON file cache round-trips;
  * no module outside ``src/repro/kernels/`` passes literal tile sizes —
    tiles come from ``kernels.autotune`` or an explicit caller override,
    never from scattered hardcoded constants.
"""

import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import vq
from repro.kernels import autotune, ops, ref

KEY = jax.random.PRNGKey(11)

# batch % bm != 0, kappa % bk != 0, kappa < bk, batch < 8 — all the ways a
# shape can disagree with a tile
RAGGED = [(100, 200, 16), (64, 300, 8), (7, 33, 5), (3, 4, 2), (130, 17, 3)]


@pytest.fixture(autouse=True)
def _fresh_tuner():
    """Each test sees a clean in-memory tuner and leaves one behind."""
    autotune.set_cache_path(None)
    autotune.reset("cache")
    yield
    autotune.set_cache_path(None)
    autotune.reset("cache")


def _case(batch, kappa, d):
    kz, kw = jax.random.split(jax.random.fold_in(KEY, batch * kappa + d))
    z = jax.random.normal(kz, (batch, d))
    w = jax.random.normal(kw, (kappa, d))
    return z, w


# -- ragged-shape parity: every route vs the oracle -------------------------

@pytest.mark.parametrize("batch,kappa,d", RAGGED)
def test_all_delta_routes_match_ref_on_ragged_shapes(batch, kappa, d):
    z, w = _case(batch, kappa, d)
    cr, sr = ref.vq_delta_ref(z, w)
    routes = {
        "full": {},                                   # fits-VMEM kernel
        "blocked_tuned": {"budget_bytes": 1024},      # fused, tuner tiles
        "blocked_forced": {"budget_bytes": 1024, "bm": 16, "bk": 128},
    }
    for name, kwargs in routes.items():
        c, s = ops.vq_delta_routed(z, w, **kwargs)
        np.testing.assert_allclose(np.asarray(c), np.asarray(cr),
                                   atol=1e-5, err_msg=name)
        np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("batch,kappa,d", RAGGED[:3])
def test_vq_assign_autotuned_matches_ref(batch, kappa, d):
    z, w = _case(batch, kappa, d)
    a, m = ops.vq_assign(z, w)                        # tiles from the tuner
    ar, mr = ref.vq_assign_ref(z, w)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(ar))
    np.testing.assert_allclose(np.asarray(m), np.asarray(mr),
                               rtol=1e-4, atol=1e-4)


def _window_case(case):
    """(zwin, w0) for one window-kernel parity case.

    ``normal``: the original small case.  ``uniform``: the training cells'
    width on [0, 1] data.  ``repeat``: four codes for 64 points, so the
    same rows win again and again and their kept norms are read after
    their own update.  ``tie``: every codebook row twice, so each step's
    argmin is a tie the first index must win."""
    kz, kw = jax.random.split(jax.random.fold_in(KEY, 99))
    if case == "normal":
        return (jax.random.normal(kz, (12, 8)),
                jax.random.normal(kw, (16, 8)))
    if case == "uniform":
        return (jax.random.uniform(kz, (10, 128)),
                jax.random.uniform(kw, (1024, 128)))
    if case == "repeat":
        return (jax.random.normal(kz, (64, 8)),
                jax.random.normal(kw, (4, 8)))
    half = jax.random.normal(kw, (8, 8))
    return jax.random.normal(kz, (10, 8)), jnp.concatenate([half, half])


@pytest.mark.parametrize("case", ["normal", "uniform", "repeat", "tie"])
def test_window_kernel_bitwise_matches_per_step_scan(case):
    zwin, w0 = _window_case(case)
    tau = zwin.shape[0]
    eps = vq.default_steps(1 + jnp.arange(tau, dtype=jnp.int32))
    w_fused = ops.vq_window(zwin, w0, eps)

    # the engine's per-step route, verbatim (mesh._step_window's scan
    # body) — the fused kernel replays these float ops exactly
    def scan_oracle(zwin, w0, eps):
        def body(carry, ze):
            w, w2 = carry
            z, e = ze
            arg, _ = ops.vq_assign_routed(z[None, :], w, w2=w2)
            k = arg[0]
            row = jax.lax.dynamic_slice_in_dim(w, k, 1)
            h = row - z[None, :]
            row = row - e * h
            w = jax.lax.dynamic_update_slice_in_dim(w, row, k, axis=0)
            w2 = jax.lax.dynamic_update_slice_in_dim(
                w2, jnp.sum(row * row, axis=1, keepdims=True), k, axis=1)
            return (w, w2), None
        w2 = jnp.sum(w0 * w0, axis=1)[None, :]
        return jax.lax.scan(body, (w0, w2), (zwin, eps))[0][0]

    w_ref = jax.jit(scan_oracle)(zwin, w0, eps)
    # fusion trades dispatches, not math: BITWISE equality, not allclose
    assert np.array_equal(np.asarray(w_fused), np.asarray(w_ref))


# -- tuner determinism ------------------------------------------------------

def test_same_shape_same_config_and_cache_hit_never_researches():
    c1 = autotune.pick_tiles(100, 200, 16)
    assert autotune.search_count() == 1
    c2 = autotune.pick_tiles(100, 200, 16)
    assert c1 == c2
    assert autotune.search_count() == 1          # hit: zero re-search
    # the pick must be feasible under the SAME formula the router uses
    assert ops.delta_vmem_bytes(200, 16, bm=c1.bm, bk=c1.bk) \
        <= ops.vmem_budget_bytes(None)
    # a different shape is a different key, not a collision
    c3 = autotune.pick_tiles(64, 300, 8)
    assert autotune.search_count() == 2
    assert autotune.tune_key("delta", 100, 200, 16) \
        != autotune.tune_key("delta", 64, 300, 8)


def test_off_mode_returns_legacy_tiles_without_caching():
    autotune.reset("off")
    cfg = autotune.pick_tiles(100, 200, 16)
    assert (cfg.bm, cfg.bk) == autotune.DEFAULT_TILES
    assert autotune.search_count() == 0


def test_json_cache_round_trips(tmp_path):
    path = tmp_path / "tiles.json"
    autotune.set_cache_path(str(path))
    autotune.reset("cache")
    c1 = autotune.pick_tiles(100, 200, 16)
    assert autotune.search_count() == 1
    assert path.exists()
    # a fresh process (reset) reloads the file: hit, zero re-search
    autotune.reset("cache")
    c2 = autotune.pick_tiles(100, 200, 16)
    assert c1 == c2
    assert autotune.search_count() == 0


def test_tune_key_is_device_scoped():
    assert autotune.device_kind() in autotune.tune_key("delta", 8, 16, 4)


# -- the tile-hygiene pin ---------------------------------------------------

def test_no_literal_tile_sizes_outside_kernels():
    """Tiles are the tuner's (or an explicit caller's) to choose: no module
    outside ``src/repro/kernels/`` may pass literal ``bm=``/``bk=`` sizes."""
    import repro
    root = pathlib.Path(next(iter(repro.__path__)))
    pat = re.compile(r"\b(bm|bk)\s*=\s*\d")
    offenders = []
    for p in sorted(root.rglob("*.py")):
        if p.relative_to(root).parts[0] == "kernels":
            continue
        for i, line in enumerate(p.read_text().splitlines(), 1):
            if pat.search(line):
                offenders.append(f"{p.relative_to(root)}:{i}: {line.strip()}")
    assert not offenders, (
        "literal kernel tile sizes outside src/repro/kernels/ "
        "(route through kernels.autotune instead):\n" + "\n".join(offenders))
