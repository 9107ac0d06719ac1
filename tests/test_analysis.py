"""Unit tests for the HLO collective accounting and the roofline model."""

import pytest

from repro.configs import registry
from repro.distributed import hlo_analysis, roofline


SAMPLE_HLO = """\
HloModule jit_step, is_scheduled=true

%add.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(%a, %b)
}

%cond.1 (p: (s32[], f32[8,4])) -> pred[] {
  %p = (s32[], f32[8,4]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(12)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%body.1 (p: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
  %p = (s32[], f32[8,4]{1,0}) parameter(0)
  %x = f32[8,4]{1,0} get-tuple-element(%p), index=1
  %ar = f32[8,4]{1,0} all-reduce(%x), channel_id=1, replica_groups=[2,4]<=[8], to_apply=%add.1
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], f32[8,4]{1,0}) tuple(%i, %ar)
}

ENTRY %main (arg: f32[8,4]) -> f32[8,4] {
  %arg = f32[8,4]{1,0} parameter(0)
  %init = (s32[], f32[8,4]{1,0}) tuple(s32[] constant(0), %arg)
  %w = (s32[], f32[8,4]{1,0}) while(%init), condition=%cond.1, body=%body.1
  %y = f32[8,4]{1,0} get-tuple-element(%w), index=1
  %ag = bf16[16,4]{1,0} all-gather(%y), channel_id=2, replica_groups=[4,2]<=[8], dimensions={0}
  ROOT %out = f32[8,4]{1,0} get-tuple-element(%w), index=1
}
"""


def test_trip_count_from_condition_constant():
    res = hlo_analysis.analyze_collectives(SAMPLE_HLO)
    assert dict(res["loops"])["body.1"] == 12


def test_collective_bytes_weighted_by_trips():
    res = hlo_analysis.analyze_collectives(SAMPLE_HLO)
    # in-loop all-reduce: f32[8,4] = 128 B x 12 trips = 1536
    assert res["bytes_by_kind"]["all-reduce"] == 128 * 12
    # entry all-gather: bf16[16,4] = 128 B x 1
    assert res["bytes_by_kind"]["all-gather"] == 128
    assert res["total_bytes"] == 128 * 12 + 128
    assert res["in_loop_bytes"] == 128 * 12
    # tpu adjustment halves the f32 all-reduce bytes
    assert res["tpu_adjusted_bytes"] == 128 * 12 / 2 + 128


def test_shape_bytes_tuple_types():
    assert hlo_analysis._shape_bytes("(f32[2,3], bf16[4])") == 24 + 8
    assert hlo_analysis._shape_bytes("pred[7]") == 7


# ---------------------------------------------------------------------------
# roofline model
# ---------------------------------------------------------------------------

def _cell(name):
    return next(s for s in registry.SHAPES if s.name == name)


def test_model_flops_scale():
    """6*N*D within a factor ~2 of the analytic total for a dense train cell
    (the extra is attention quadratic + remat)."""
    cfg = registry.get_config("granite_8b")
    fl = roofline.cell_flops(cfg, _cell("train_4k"))
    assert fl["model_flops"] < fl["total"] < 4 * fl["model_flops"]


def test_decode_is_memory_bound_in_model():
    cfg = registry.get_config("granite_8b")
    mesh = roofline.mesh_shape(False)
    terms = roofline.roofline_terms(cfg, _cell("decode_32k"), mesh, 1e6)
    assert terms["dominant"] == "memory"


def test_replication_waste_for_nondivisible_heads():
    cfg = registry.get_config("starcoder2_7b")  # 36 heads % 16 != 0
    w = roofline.replication_waste(cfg, roofline.mesh_shape(False))
    assert w > 2.0
    cfg2 = registry.get_config("granite_8b")  # 32 heads
    assert roofline.replication_waste(
        cfg2, roofline.mesh_shape(False)) == 1.0


def test_multipod_halves_per_device_flops():
    cfg = registry.get_config("granite_8b")
    c = _cell("train_4k")
    t1 = roofline.roofline_terms(cfg, c, roofline.mesh_shape(False), 0.0)
    t2 = roofline.roofline_terms(cfg, c, roofline.mesh_shape(True), 0.0)
    assert t2["t_compute"] == pytest.approx(t1["t_compute"] / 2, rel=1e-6)


# ---------------------------------------------------------------------------
# masked-loop trip inference + dtype table (profiling satellites)
# ---------------------------------------------------------------------------

# an engine-shaped async loop: the carried tuple holds a pred[8] arrival
# mask whose leading dim (8) would outvote the data dims' mode if preds
# were counted — the condition constant is absent, forcing the fallback
MASKED_LOOP_HLO = """\
HloModule masked

%add.2 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(%a, %b)
}

%cond.2 (p: (s32[], pred[8], f32[40,4], f32[40,4])) -> pred[] {
  %p = (s32[], pred[8]{0}, f32[40,4]{1,0}, f32[40,4]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] parameter-like(%p)
  ROOT %lt = pred[] compare(%i, %i), direction=LT
}

%body.2 (p: (s32[], pred[8], f32[40,4], f32[40,4])) -> (s32[], pred[8], f32[40,4], f32[40,4]) {
  %p = (s32[], pred[8]{0}, f32[40,4]{1,0}, f32[40,4]{1,0}) parameter(0)
  %mask = pred[8]{0} get-tuple-element(%p), index=1
  %x = f32[40,4]{1,0} get-tuple-element(%p), index=2
  %ar = f32[40,4]{1,0} all-reduce(%x), channel_id=3, replica_groups=[1,8]<=[8], to_apply=%add.2
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], pred[8]{0}, f32[40,4]{1,0}, f32[40,4]{1,0}) tuple(%i, %mask, %ar, %ar)
}

ENTRY %main (arg: f32[40,4]) -> f32[40,4] {
  %arg = f32[40,4]{1,0} parameter(0)
  %m0 = pred[8]{0} constant({...})
  %init = (s32[], pred[8]{0}, f32[40,4]{1,0}, f32[40,4]{1,0}) tuple(s32[] constant(0), %m0, %arg, %arg)
  %w = (s32[], pred[8]{0}, f32[40,4]{1,0}, f32[40,4]{1,0}) while(%init), condition=%cond.2, body=%body.2
  ROOT %out = f32[40,4]{1,0} get-tuple-element(%w), index=2
}
"""


def test_fallback_trip_mode_skips_pred_dims():
    """The pred-carrying mask must not skew the leading-dim mode: the data
    leaves' leading dim (40) is the scan length, not the mask's 8."""
    res = hlo_analysis.analyze_collectives(MASKED_LOOP_HLO)
    assert dict(res["loops"])["body.2"] == 40
    assert hlo_analysis._leading_dims(
        "(s32[], pred[8], f32[40,4], f32[40,4])") == [40, 40]


def test_dtype_table_complex_and_longest_first():
    # c64 was in the byte table but unreachable through the old regex;
    # c128 joins it — both must parse, and f8e4m3fn must not lex as "f8"+junk
    assert hlo_analysis._shape_bytes("c64[2]") == 16
    assert hlo_analysis._shape_bytes("c128[2]") == 32
    assert hlo_analysis._shape_bytes("f8e4m3fn[4]") == 4
    assert hlo_analysis._shape_bytes("(c64[3], f32[2])") == 24 + 8


# ---------------------------------------------------------------------------
# VqCell roofline (the profiler's analytic terms)
# ---------------------------------------------------------------------------

def test_vq_cell_flops_and_bytes_scale():
    c1 = roofline.VqCell(d=8, kappa=16, tau=50)
    c2 = roofline.VqCell(d=8, kappa=16, tau=100)
    # per-window step work is linear in tau; merge/eval terms are not
    assert c2.window_flops() - c2.merge_flops() == pytest.approx(
        2 * (c1.window_flops() - c1.merge_flops()))
    assert c1.merge_collective_bytes() == 16 * 8 * 4
    # hand count of one step: distance 2kd, argmin k, delta 2kd, update 3kd
    assert c1.step_flops() == 2 * 16 * 8 + 16 + 2 * 16 * 8 + 3 * 16 * 8


def test_vq_roofline_terms_bound_and_dominate():
    cell = roofline.VqCell(d=8, kappa=16, tau=50, n_eval=100)
    terms = roofline.vq_roofline_terms(
        cell, collective_bytes_per_window=cell.merge_collective_bytes())
    assert terms["dominant"] in ("compute", "memory", "collective")
    assert terms["window_time_bound_s"] == pytest.approx(
        max(terms["t_compute"], terms["t_memory"], terms["t_collective"]))
    # tiny shapes on TPU-class peaks: every term strictly positive
    assert all(terms[k] > 0 for k in ("t_compute", "t_memory",
                                      "t_collective"))


def test_vq_roofline_terms_default_to_analytic_merge_bytes():
    """No compiled program available -> the dense-merge lower bound."""
    cell = roofline.VqCell(d=8, kappa=16, tau=10)
    terms = roofline.vq_roofline_terms(cell)
    assert terms["collective_bytes"] == cell.merge_collective_bytes()
    assert terms["t_collective"] == pytest.approx(
        cell.merge_collective_bytes() / roofline.MODEL_PEAKS.ici_bw)


class _Dev:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize("platform,kind,want", [
    ("cpu", "cpu", "model"),                 # off-TPU: the model's chip
    ("tpu", "TPU v5 lite", "v5e"),           # a v5e: its published peaks
    ("tpu", "TPU v9 imaginary", KeyError),   # unknown TPU: never a default
])
def test_device_peaks_keyed_by_device_kind(monkeypatch, platform, kind,
                                           want):
    import jax

    monkeypatch.setattr(jax, "devices", lambda: [_Dev(platform, kind)])
    if want is KeyError:
        with pytest.raises(KeyError, match="no roofline peaks"):
            roofline.device_peaks()
        return
    peaks = roofline.device_peaks()
    assert peaks == roofline.PEAKS["TPU v5 lite"] == roofline.MODEL_PEAKS
    assert (peaks.flops, peaks.hbm_bw, peaks.ici_bw) == (197e12, 819e9,
                                                         200e9)
