"""Three-term roofline model for every (arch x shape x mesh) cell.

Per-chip peaks live in ``PEAKS``, keyed by ``jax.Device.device_kind``:

  compute term    = device_FLOPs / peak_FLOP/s
  memory term     = device_HBM_bytes / HBM_bw
  collective term = device_collective_bytes / ICI_bw

Because XLA's ``cost_analysis`` counts ``while`` (scan) bodies once, the
compute and memory terms are built ANALYTICALLY from the model config and
the known sharding policy (the same arithmetic a perf engineer does by hand)
and cross-checked against cost_analysis; the collective term comes from the
trip-count-corrected HLO parse (``hlo_analysis``).  All terms are per-device
seconds for ONE step of the cell's kind.
"""

from __future__ import annotations

import dataclasses

from repro.configs.registry import ShapeCell
from repro.models.common import ModelConfig


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks."""

    flops: float     # bf16 FLOP/s
    hbm_bw: float    # HBM bytes/s
    ici_bw: float    # chip-to-chip interconnect bytes/s


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
# 1,600 Gbit/s chip-to-chip interconnect.
PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=1600e9 / 8),
}

# The chip the analytic model targets where no TPU is attached: the dry
# run's placeholder devices, and CPU runs that rank tiles or attribute cost.
MODEL_PEAKS = PEAKS["TPU v5 lite"]


def device_peaks() -> Peaks:
    """Peaks of the first JAX device: ``MODEL_PEAKS`` off-TPU; a TPU kind
    missing from ``PEAKS`` is an error, never another chip's numbers."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return MODEL_PEAKS
    if dev.device_kind not in PEAKS:
        raise KeyError(f"no roofline peaks for TPU kind {dev.device_kind!r}; "
                       f"add its published peaks to PEAKS")
    return PEAKS[dev.device_kind]


@dataclasses.dataclass(frozen=True)
class MeshShape:
    pod: int
    data: int
    model: int

    @property
    def n_devices(self) -> int:
        return self.pod * self.data * self.model

    @property
    def dp(self) -> int:
        return self.pod * self.data


def mesh_shape(multi_pod: bool) -> MeshShape:
    return MeshShape(2 if multi_pod else 1, 16, 16)


def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


# ---------------------------------------------------------------------------
# analytic FLOPs (global, then / n_devices with replication waste)
# ---------------------------------------------------------------------------

def _attn_proj_flops_token(cfg: ModelConfig) -> int:
    """Per-token projection matmul FLOPs for one attention layer (fwd)."""
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return 2 * d * (hq * dh) * 2 + 2 * d * (hkv * dh) * 2  # q,o + k,v


def _attn_score_flops_token(cfg: ModelConfig, ctx: int, window: int = 0) -> int:
    """Per-token score+value FLOPs for context length ``ctx`` (fwd)."""
    eff = min(ctx, window) if window else ctx
    return 2 * 2 * cfg.n_heads * cfg.head_dim * eff  # qk^T and pv


def _mlp_flops_token(cfg: ModelConfig) -> int:
    if cfg.family == "moe":
        return 2 * 3 * cfg.d_model * cfg.d_ff * cfg.top_k
    if cfg.family == "encdec":
        return 2 * 2 * cfg.d_model * cfg.d_ff
    return 2 * 3 * cfg.d_model * cfg.d_ff


def _ssm_flops_token(cfg: ModelConfig) -> int:
    d, di, n, h, p = (cfg.d_model, cfg.d_inner, cfg.ssm_state,
                      cfg.ssm_heads, cfg.ssm_headdim)
    proj = 2 * d * (2 * di + 2 * n + h)
    out = 2 * di * d
    # SSD: intra-chunk quadratic (chunk q=128) + state update/output
    q = 128
    intra = 2 * h * p * q + 2 * q * n  # per token vs chunk
    state = 2 * 2 * h * p * n
    return proj + out + intra + state


def layer_flops_token(cfg: ModelConfig, ctx: int, decode: bool = False) -> float:
    """Fwd FLOPs per token per layer (weighted mix for hybrid schedules)."""
    win = cfg.window
    f = 0.0
    if cfg.family in ("dense", "moe", "vlm", "encdec"):
        f += _attn_proj_flops_token(cfg)
        f += _attn_score_flops_token(cfg, ctx)
        f += _mlp_flops_token(cfg)
        if cfg.family == "encdec":  # cross attention
            f += 2 * cfg.d_model * cfg.n_heads * cfg.head_dim * 2
            f += 2 * 2 * cfg.n_heads * cfg.head_dim * cfg.encoder_frames
    elif cfg.family == "ssm":
        f += _ssm_flops_token(cfg)
    elif cfg.family == "hybrid":
        glob = 3 / cfg.n_layers
        eff = ctx if not win else (glob * ctx + (1 - glob) * min(ctx, win))
        f += _attn_proj_flops_token(cfg)
        f += _attn_score_flops_token(cfg, int(eff))
        f += _ssm_flops_token(cfg)
        f += _mlp_flops_token(cfg)
    return f


def cell_flops(cfg: ModelConfig, cell: ShapeCell) -> dict:
    """Global FLOPs for one step of the cell (fwd [+bwd+remat for train])."""
    b, t = cell.global_batch, cell.seq_len
    if cell.kind == "decode":
        tokens = b  # one new token per sequence
        ctx = t
        per_tok = layer_flops_token(cfg, ctx, decode=True) * cfg.n_layers
        head = 2 * cfg.d_model * cfg.vocab
        fwd = tokens * (per_tok + head)
        return {"fwd": fwd, "total": fwd,
                "model_flops": 2 * cfg.active_params() * tokens}
    tokens = b * t
    # mean causal context = t/2
    per_tok = layer_flops_token(cfg, t // 2) * cfg.n_layers
    if cfg.family == "encdec":
        enc_tok = cell.global_batch * cfg.encoder_frames
        enc = enc_tok * (_attn_proj_flops_token(cfg)
                         + _attn_score_flops_token(cfg, cfg.encoder_frames)
                         + 2 * 2 * cfg.d_model * cfg.d_ff) * cfg.encoder_layers
    else:
        enc = 0
    head = 2 * cfg.d_model * cfg.vocab
    fwd = tokens * (per_tok + head) + enc
    if cell.kind == "train":
        total = fwd * 4  # bwd = 2x fwd, full remat = +1x fwd
        model = 6 * cfg.active_params() * tokens
    else:
        total = fwd
        model = 2 * cfg.active_params() * tokens
    return {"fwd": fwd, "total": total, "model_flops": model}


def replication_waste(cfg: ModelConfig, mesh: MeshShape) -> float:
    """FLOP multiplier >= 1 for layers whose TP sharding falls back to
    replication (non-divisible head counts): those FLOPs run on every
    'model'-axis device instead of 1/model of them."""
    tp = mesh.model
    if cfg.family == "ssm":
        return 1.0
    hq_ok = _div(cfg.n_heads, tp)
    if hq_ok:
        return 1.0
    # fraction of per-token layer flops that is attention
    ctx = 2048  # representative
    attn = _attn_proj_flops_token(cfg) + _attn_score_flops_token(cfg, ctx)
    total = layer_flops_token(cfg, ctx)
    frac = attn / total
    return (1 - frac) + frac * tp


# ---------------------------------------------------------------------------
# analytic HBM bytes per device
# ---------------------------------------------------------------------------

def cell_bytes(cfg: ModelConfig, cell: ShapeCell, mesh: MeshShape,
               *, seq_parallel: bool = True) -> dict:
    """Per-device HBM traffic for one step (dominant terms)."""
    n = mesh.n_devices
    params = cfg.n_params()
    p_bytes = params * 2  # bf16
    b, t = cell.global_batch, cell.seq_len
    d = cfg.d_model

    if cell.kind == "decode":
        # weights are read once per token step: all local param shards
        # (decode is memory-bound on weights + cache read/write)
        weight_read = p_bytes / mesh.model  # TP-sharded; DP replicas each read
        if cfg.family in ("dense", "moe", "vlm", "encdec", "hybrid"):
            kv = (cfg.n_layers * 2 * b * t * cfg.n_kv_heads * cfg.head_dim * 2)
            cache = kv / n  # sharded over batch x seq
        else:
            cache = 0
        if cfg.family in ("ssm", "hybrid"):
            cache += (cfg.n_layers * b * cfg.ssm_heads * cfg.ssm_headdim
                      * cfg.ssm_state * 4 * 2) / max(mesh.model, 1)
        if cfg.family == "moe":
            weight_read = (p_bytes * cfg.active_params() / params) / mesh.model
        act = b * cfg.n_layers * d * 2 * 8 / n
        total = weight_read + cache + act
        return {"total": total, "weights": weight_read, "cache": cache}

    # train / prefill: per-device = local params traffic + activations
    tp_shard = mesh.model
    fsdp = mesh.data if uses_fsdp_name(cfg) else 1
    local_params = p_bytes / tp_shard
    passes = 3 if cell.kind == "train" else 1  # fwd read, bwd read, grad write
    opt = (params * 4 * 2 * 2 / (tp_shard * fsdp)) if cell.kind == "train" else 0
    # activations: residual stream + attention internals, with remat ~2x fwd
    toks_local = b * t / (mesh.dp * (tp_shard if seq_parallel else 1))
    act_unit = toks_local * d * 2
    act = act_unit * cfg.n_layers * 12 * (2 if cell.kind == "train" else 1)
    total = local_params * passes + opt + act
    return {"total": total, "weights": local_params * passes, "opt": opt,
            "activations": act}


def uses_fsdp_name(cfg: ModelConfig) -> bool:
    return cfg.name in {
        "granite-34b", "command-r-35b", "internvl2-76b",
        "moonshot-v1-16b-a3b", "starcoder2-7b",
    }


# ---------------------------------------------------------------------------
# VQ cells — the paper's inner loop, per worker (= per device on the mesh)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class VqCell:
    """Shapes of one VQ *window* on ONE device (= one paper worker).

    The engine runs a window as a fused ``lax.scan`` of ``tau`` stochastic
    VQ steps (assign -> delta -> update, the eq. 3/8 inner loop), then an
    eval-set distortion probe and the cross-worker merge.  The analytic
    flop/byte terms below are the hand counts for those phases, specialized
    to the ``(d, kappa, tau, bm)`` shapes the Pallas path tiles over —
    deliberately the same arithmetic style as ``benchmarks/run.py``'s
    ``bench_vq_kernel`` so the numbers cross-check.
    """

    d: int                 # point dimensionality
    kappa: int             # codebook size
    tau: int               # steps per window (merge period)
    n_eval: int = 0        # eval points scored per window (0 = no probe)
    bm: int = 128          # Pallas block rows (HBM tiling granularity)
    dtype_bytes: int = 4   # codebook/point element width (f32)
    bk: int = 128          # Pallas codebook-block rows (blocked/fused regime)

    def step_flops(self) -> float:
        """One stochastic VQ step: distances ``2*kappa*d`` (|z-w|^2 via the
        expanded dot), argmin ``kappa``, one-hot delta scatter ``2*kappa*d``,
        and the eq.-8 update (scale + add + displacement) ``3*kappa*d``."""
        k, d = self.kappa, self.d
        return 2 * k * d + k + 2 * k * d + 3 * k * d

    def eval_flops(self) -> float:
        """Distortion probe: full distance matrix + min-reduce over codes."""
        return 2 * self.n_eval * self.kappa * self.d + 2 * self.n_eval * self.kappa

    def merge_flops(self) -> float:
        """Post-collective combine: scale + add over the codebook."""
        return 3 * self.kappa * self.d

    def window_flops(self) -> float:
        """Device FLOPs for one full window (tau steps + probe + merge)."""
        return self.tau * self.step_flops() + self.eval_flops() + self.merge_flops()

    def window_hbm_bytes(self) -> float:
        """Dominant per-window HBM traffic: each step re-reads the codebook
        (twice: assign + update) and streams its point; the probe streams the
        eval shard; the merge reads + writes the codebook once."""
        b = self.dtype_bytes
        k, d = self.kappa, self.d
        per_step = 2 * k * d * b + d * b + k * b     # codebook x2, point, codes
        probe = self.n_eval * d * b
        merge = 2 * k * d * b
        return self.tau * per_step + probe + merge

    def merge_collective_bytes(self) -> float:
        """Logical all-reduce payload of one dense merge: the codebook."""
        return self.kappa * self.d * self.dtype_bytes

    # -- blocked/fused delta kernel terms (the autotuner's objective) ------

    def delta_grid(self, batch: int) -> tuple[int, int]:
        """(codebook_blocks, batch_blocks) of the fused blocked kernel's
        two-sweep grid, after ``ops.py``'s padding to tile multiples."""
        kb = -(-self.kappa // self.bk)
        nb = -(-batch // self.bm)
        return kb, nb

    def delta_flops(self, batch: int) -> float:
        """One fused assign+delta dispatch over a (batch, d) block of
        points: the distance sweep's expanded dot + argmin and the
        accumulate sweep's one-hot matmul scatter."""
        k, d = self.kappa, self.d
        distance = 2 * batch * k * d + batch * k
        accumulate = 2 * batch * k * d + batch * k
        return distance + accumulate

    def delta_hbm_bytes(self, batch: int) -> float:
        """HBM traffic of the fused blocked kernel INCLUDING refetches:
        both sweeps re-stream each (bm, d) point block once per codebook
        block and each (bk, d) codebook block once per batch block — the
        tile-size-dependent term the autotuner trades against VMEM
        residency (larger tiles => fewer refetches => fewer bytes)."""
        kb, nb = self.delta_grid(batch)
        b = self.dtype_bytes
        k, d = self.kappa, self.d
        sweeps = 2 * (kb * batch * d * b + nb * k * d * b)
        outputs = k * d * b + k * b + 2 * batch * b   # zsum, counts, arg+min
        return sweeps + outputs


def vq_roofline_terms(cell: VqCell,
                      collective_bytes_per_window: float | None = None,
                      peaks: Peaks = MODEL_PEAKS) -> dict:
    """Per-window roofline terms (seconds) for one VQ worker-device.

    ``collective_bytes_per_window`` should come from the trip-count-
    corrected HLO parse of the *actual* compiled program
    (``hlo_analysis.analyze_collectives``); the analytic
    ``merge_collective_bytes`` is only the dense-merge lower bound used
    when no compiled program is available.
    """
    coll = (cell.merge_collective_bytes()
            if collective_bytes_per_window is None
            else collective_bytes_per_window)
    terms = {
        "compute": cell.window_flops() / peaks.flops,
        "memory": cell.window_hbm_bytes() / peaks.hbm_bw,
        "collective": coll / peaks.ici_bw,
    }
    dominant = max(terms, key=terms.get)
    return {
        **{f"t_{k}": v for k, v in terms.items()},
        "dominant": dominant,
        "window_flops": cell.window_flops(),
        "window_hbm_bytes": cell.window_hbm_bytes(),
        "collective_bytes": coll,
        "window_time_bound_s": max(terms.values()),   # perfect-overlap bound
    }


# ---------------------------------------------------------------------------
# terms
# ---------------------------------------------------------------------------

def roofline_terms(cfg: ModelConfig, cell: ShapeCell, mesh: MeshShape,
                   collective_bytes_per_dev: float) -> dict:
    fl = cell_flops(cfg, cell)
    waste = replication_waste(cfg, mesh)
    dev_flops = fl["total"] * waste / mesh.n_devices
    by = cell_bytes(cfg, cell, mesh)

    t_compute = dev_flops / MODEL_PEAKS.flops
    t_memory = by["total"] / MODEL_PEAKS.hbm_bw
    t_coll = collective_bytes_per_dev / MODEL_PEAKS.ici_bw
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_coll}
    dominant = max(terms, key=terms.get)
    step_time = max(terms.values())  # perfect-overlap bound
    mfu = ((fl["model_flops"] / mesh.n_devices / MODEL_PEAKS.flops)
           / step_time if step_time > 0 else 0.0)
    return {
        **{f"t_{k}": v for k, v in terms.items()},
        "dominant": dominant,
        "device_flops": dev_flops,
        "device_bytes": by["total"],
        "bytes_detail": by,
        "model_flops": fl["model_flops"],
        "useful_ratio": fl["model_flops"] / (fl["total"] * waste),
        "replication_waste": waste,
        "step_time_bound_s": step_time,
        "mfu_bound": mfu,
    }
