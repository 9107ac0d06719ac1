"""Roofline-driven (bm, bk) tile selection with a deterministic cache.

The Pallas kernels used to run on hardcoded ``bm=128``/``bk=128`` tiles
regardless of shape.  This module picks tiles per
``(kind, batch, kappa, d, dtype_bytes, device_kind)`` from the
``distributed.roofline.VqCell`` analytic model: among the candidate tiles
whose residency fits the VMEM budget (``ops.delta_vmem_bytes`` — the SAME
formula the runtime router uses, so the two can never disagree about what
fits), minimize the roofline time bound

    max(delta_flops / peak_flops, delta_hbm_bytes / hbm_bw)

where ``delta_hbm_bytes`` counts the blocked kernel's refetch traffic —
larger tiles mean fewer refetches, so the model pushes tiles as large as
the budget allows, then grid size breaks ties deterministically.

Two modes, set once at launch (``--autotune {off,cache}``):

  * ``off``    — legacy fixed (128, 128) tiles, no cache touched.
  * ``cache``  — model-picked tiles, memoized in-process and (optionally)
                 in a JSON file (``REPRO_AUTOTUNE_CACHE=path`` or
                 ``set_cache_path``).  Same shape => same config, always.

The JSON cache is keyed by the full tune key INCLUDING the device kind, so
a file tuned on one accelerator never leaks tiles to another.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading

from repro.distributed.roofline import VqCell, device_peaks

MODES = ("off", "cache")
DEFAULT_TILES = (128, 128)          # the pre-autotune hardcoded tiles
CANDIDATE_TILES = (8, 16, 32, 64, 128, 256, 512)


@dataclasses.dataclass(frozen=True)
class TileConfig:
    bm: int
    bk: int


class _TunerState:
    def __init__(self):
        self.mode = "cache"
        self.cache: dict[str, TileConfig] = {}
        self.cache_path: str | None = None
        self.file_loaded = False
        self.searches = 0            # model evaluations (cache misses)
        self.lock = threading.Lock()


_STATE = _TunerState()


def set_mode(mode: str) -> None:
    if mode not in MODES:
        raise ValueError(f"autotune mode must be one of {MODES}, got {mode!r}")
    _STATE.mode = mode


def get_mode() -> str:
    return _STATE.mode


def set_cache_path(path: str | None) -> None:
    """Point the tuner at a JSON cache file (None = in-memory only)."""
    _STATE.cache_path = path
    _STATE.file_loaded = False


def reset(mode: str | None = None) -> None:
    """Drop all cached configs and counters (tests use this)."""
    with _STATE.lock:
        _STATE.cache.clear()
        _STATE.searches = 0
        _STATE.file_loaded = False
        if mode is not None:
            _STATE.mode = mode


def search_count() -> int:
    """How many cache misses have been resolved since the last reset."""
    return _STATE.searches


def device_kind() -> str:
    import jax
    dev = jax.devices()[0]
    return f"{dev.platform}:{getattr(dev, 'device_kind', '?')}"


def tune_key(kind: str, batch: int, kappa: int, d: int,
             dtype_bytes: int = 4, device: str | None = None) -> str:
    device = device_kind() if device is None else device
    return f"{kind}|b{batch}|k{kappa}|d{d}|e{dtype_bytes}|{device}"


def _candidates(batch: int, kappa: int, d: int, *, budget_bytes: int,
                dtype_bytes: int) -> list[TileConfig]:
    """VMEM-feasible (bm, bk) pairs.  bm beyond the (8-row-padded) batch or
    bk beyond the codebook only pads work, so those are clamped out."""
    from repro.kernels import ops

    bm_cap = max(8, batch)
    bk_cap = max(8, kappa)
    bms = sorted({min(c, bm_cap) for c in CANDIDATE_TILES})
    bks = sorted({min(c, bk_cap) for c in CANDIDATE_TILES})
    out = []
    for bm in bms:
        for bk in bks:
            need = ops.delta_vmem_bytes(kappa, d, bm=bm, bk=bk,
                                        dtype_bytes=dtype_bytes)
            if need <= budget_bytes:
                out.append(TileConfig(bm=bm, bk=bk))
    if not out:                       # degenerate budget: smallest tiles
        out.append(TileConfig(bm=min(bms), bk=min(bks)))
    return out


def model_time(cfg: TileConfig, batch: int, kappa: int, d: int,
               dtype_bytes: int = 4) -> float:
    """Roofline time bound (s) for one fused delta dispatch at these tiles."""
    cell = VqCell(d=d, kappa=kappa, tau=1, bm=cfg.bm, bk=cfg.bk,
                  dtype_bytes=dtype_bytes)
    peaks = device_peaks()
    return max(cell.delta_flops(batch) / peaks.flops,
               cell.delta_hbm_bytes(batch) / peaks.hbm_bw)


def _rank(cands: list[TileConfig], batch: int, kappa: int, d: int,
          dtype_bytes: int) -> list[TileConfig]:
    """Deterministic model ranking: roofline time, then grid steps, then
    the larger tile — a pure function of the tune key."""
    def score(cfg: TileConfig):
        cell = VqCell(d=d, kappa=kappa, tau=1, bm=cfg.bm, bk=cfg.bk,
                      dtype_bytes=dtype_bytes)
        kb, nb = cell.delta_grid(batch)
        return (model_time(cfg, batch, kappa, d, dtype_bytes),
                2 * kb * nb, -cfg.bm, -cfg.bk)
    return sorted(cands, key=score)


def _load_file_cache() -> None:
    path = _STATE.cache_path or os.environ.get("REPRO_AUTOTUNE_CACHE")
    _STATE.file_loaded = True
    if not path or not os.path.exists(path):
        return
    try:
        with open(path) as f:
            raw = json.load(f)
    except (OSError, ValueError):
        return
    for k, v in raw.items():
        if (isinstance(v, (list, tuple)) and len(v) == 2
                and k not in _STATE.cache):
            _STATE.cache[k] = TileConfig(bm=int(v[0]), bk=int(v[1]))


def _save_file_cache() -> None:
    path = _STATE.cache_path or os.environ.get("REPRO_AUTOTUNE_CACHE")
    if not path:
        return
    try:
        with open(path, "w") as f:
            json.dump({k: [c.bm, c.bk] for k, c in
                       sorted(_STATE.cache.items())}, f, indent=0,
                      sort_keys=True)
    except OSError:
        pass


def pick_tiles(batch: int, kappa: int, d: int, *, kind: str = "delta",
               budget_bytes: int | None = None,
               dtype_bytes: int = 4) -> TileConfig:
    """Tuned (bm, bk) for one kernel shape — THE entry point.

    ``off`` returns the legacy fixed tiles.  Otherwise the config comes
    from the cache (file-backed if configured) or is the model's pick,
    computed once and cached.
    """
    if _STATE.mode == "off":
        return TileConfig(*DEFAULT_TILES)
    from repro.kernels import ops

    budget = ops.vmem_budget_bytes(budget_bytes)
    key = tune_key(kind, batch, kappa, d, dtype_bytes)
    with _STATE.lock:
        if not _STATE.file_loaded:
            _load_file_cache()
        hit = _STATE.cache.get(key)
        if hit is not None:
            return hit
        best = _rank(_candidates(batch, kappa, d, budget_bytes=budget,
                                 dtype_bytes=dtype_bytes),
                     batch, kappa, d, dtype_bytes)[0]
        _STATE.searches += 1
        _STATE.cache[key] = best
        _save_file_cache()
        return best
