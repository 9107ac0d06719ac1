"""The main path's Pallas kernels compile for a TPU v5e, at real widths.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology and refuses what the chip's would (a primitive Mosaic cannot
lower, a slice off the tiling, too much VMEM).  Each test asserts that the
compiled program holds the kernel (``tpu_custom_call``), named as the chip
smoke expects.  The topology is described inside a fixture, never while a
module is imported, and the persistent compilation cache is off around
these compiles: their entries could not be read back without a chip.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.comm import ring
from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _f32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("d,kappa", [(128, 1024), (128, 2048), (8, 16)])
def test_window_kernel_compiles(one_chip, d, kappa):
    tau = 10
    text = _compiled_text(
        lambda z, w, eps: ops.vq_window(z, w, eps, interpret=False),
        _f32(one_chip, tau, d), _f32(one_chip, kappa, d),
        _f32(one_chip, tau))
    assert "tpu_custom_call" in text and "/vq_window/" in text


def test_pq_window_kernel_compiles(one_chip):
    """PQ16x256 on SIFT: m=16 sub-codebooks of k*=256 codes over 8-d
    sub-vectors, tau=10."""
    tau, m, k, ds = 10, 16, 256, 8
    text = _compiled_text(
        lambda z, w, eps: ops.pq_window(z, w, eps, interpret=False),
        _f32(one_chip, tau, m * ds), _f32(one_chip, m, k, ds),
        _f32(one_chip, tau))
    assert "tpu_custom_call" in text and "/pq_window/" in text


class _Compiled(Exception):
    pass


def test_pq_segment_names_its_kernel_and_probe(topo, monkeypatch):
    """A PQ16x256 training segment compiled for one chip: the ``pq_window``
    kernel sits under the ``local_window`` scope and the PQ probe under
    ``eval_probe``, the op paths a profiler trace shows."""
    import re

    from repro.engine import MeshExecutor

    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    mesh = Mesh(np.array(topo.devices[:1]), ("workers",))
    ex = MeshExecutor(mesh=mesh)
    texts = []

    def compile_only(cache_key, build, *args):
        specs = (P(), P()) + (P("workers"),) * (len(args) - 2)
        shapes = [jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, s)), a)
            for a, s in zip(args, specs)]
        texts.append(build().lower(*shapes).compile().as_text())
        raise _Compiled

    ex._call_compiled = compile_only
    with pytest.raises(_Compiled):
        ex.run_segment("delta", jnp.zeros((16, 256, 8)),
                       jnp.zeros((1, 200, 128)), jnp.zeros((1, 512, 128)),
                       tau=10, mesh=mesh)
    paths = [p.split("/") for p in re.findall(r'op_name="([^"]*)"', texts[0])]
    assert any("local_window" in p and "pq_window" in p for p in paths)
    assert any("eval_probe" in p for p in paths)
    assert "tpu_custom_call" in texts[0]


def test_per_step_delta_kernel_compiles(one_chip):
    """One point against kappa=4096 (the window is past the VMEM budget):
    the routed step pads to bm=8 rows and runs the full-codebook kernel."""
    kappa, d = 4096, 128
    assert not ops.window_fits_vmem(kappa, d, 10)
    text = _compiled_text(
        lambda z, w: ops.vq_delta_routed(z, w, interpret=False),
        _f32(one_chip, 1, d), _f32(one_chip, kappa, d))
    assert "tpu_custom_call" in text and "/vq_delta/" in text


def test_per_step_route_compiles_with_one_assign_block(one_chip,
                                                      monkeypatch):
    """A tau=10 window at kappa=4096, d=128 (past the window kernel's VMEM
    budget): one ``vq_assign`` call a step over the whole codebook as one
    block, a grid of one, and no ``vq_delta``."""
    import re

    from repro.engine.mesh import _local_window

    kappa, d, tau = 4096, 128, 10
    assert not ops.window_fits_vmem(kappa, d, tau)
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    lowered = jax.jit(lambda w, z, t: _local_window(
        w, z, t, eps0=0.5, decay=1.0, use_pallas=True)[1]).lower(
        _f32(one_chip, kappa, d), _f32(one_chip, tau, d),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    stablehlo = lowered.as_text()
    assert re.findall(r'kernel_name = "(\w+)"', stablehlo) == ["vq_assign"]
    assert ("iteration_bounds = array<i64: 1, 1>"
            in _without_kernel_locations(stablehlo))
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text and "/vq_assign/" in text
    assert "/vq_delta/" not in text


def _without_kernel_locations(text: str) -> str:
    """Lowered text with each Mosaic kernel body printed without its
    debug locations (they name the file and line of each call)."""
    import base64
    import re

    from jax._src.lib.mlir import ir

    def asm(m):
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(m.group(1)))
            return module.operation.get_asm(enable_debug_info=False)
    return re.sub(r"\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22", asm, text)


def test_serve_lookup_program_unchanged_without_norms(one_chip):
    """Serving calls ``vq_assign`` without kept norms: its program is the
    one the wrapper built before it took them (copied below as it was)."""
    import functools

    from jax.experimental import pallas as pl

    from repro.kernels import vq_assign as _k

    def assign_pallas(z, w, *, bm, bk, kappa_valid, interpret):
        batch, d = z.shape
        kappa, _ = w.shape
        z32 = z.astype(jnp.float32)
        w32 = w.astype(jnp.float32)
        z2 = jnp.sum(z32 * z32, axis=1, keepdims=True)
        w2 = jnp.sum(w32 * w32, axis=1)[None, :]
        assign, mind = pl.pallas_call(
            functools.partial(_k._assign_kernel, bk=bk,
                              kappa_valid=kappa_valid),
            name="vq_assign",
            grid=(batch // bm, kappa // bk),
            in_specs=[pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
                      pl.BlockSpec((bk, d), lambda i, j: (j, 0)),
                      pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
                      pl.BlockSpec((1, bk), lambda i, j: (0, j))],
            out_specs=[pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
                       pl.BlockSpec((bm, 1), lambda i, j: (i, 0))],
            out_shape=[jax.ShapeDtypeStruct((batch, 1), jnp.int32),
                       jax.ShapeDtypeStruct((batch, 1), jnp.float32)],
            interpret=interpret,
        )(z, w, z2, w2)
        return assign[:, 0], mind[:, 0]

    @functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
    def _vq_assign(z, w, *, bm, bk, interpret):
        batch, kappa = z.shape[0], w.shape[0]
        bm_ = min(bm, max(ops._bm_floor(interpret), batch))
        zp = ops._pad_rows(z, bm_)
        wp = ops._pad_rows(w, bk)
        assign, mind = assign_pallas(zp, wp, bm=bm_,
                                     bk=min(bk, wp.shape[0]),
                                     kappa_valid=kappa, interpret=interpret)
        return assign[:batch], mind[:batch]

    shapes = (_f32(one_chip, 128, 128), _f32(one_chip, 4096, 128))
    bm, bk = ops._tiles(*shapes, None, None, "assign")
    now, before = (
        _without_kernel_locations(
            f.lower(*shapes, bm=bm, bk=bk, interpret=False).as_text())
        for f in (ops._vq_assign, _vq_assign))
    assert "tpu_custom_call" in now
    assert now == before


def test_assign_kernel_compiles(one_chip):
    """The serving lookup's kernel, with the tiles the tuner picks."""
    text = _compiled_text(lambda z, w: ops.vq_assign(z, w, interpret=False),
                          _f32(one_chip, 128, 128), _f32(one_chip, 4096, 128))
    assert "tpu_custom_call" in text and "/vq_assign/" in text


def test_blocked_delta_kernel_compiles(one_chip):
    text = _compiled_text(
        lambda z, w: ops.vq_delta_blocked(z, w, bm=128, bk=512,
                                          interpret=False),
        _f32(one_chip, 128, 128), _f32(one_chip, 16384, 128))
    assert "tpu_custom_call" in text and "/vq_delta_blocked/" in text


def test_ring_compiles_on_four_devices(topo):
    """The ring all-reduce of a kappa=1024, d=128 codebook over a 4-device
    mesh: RDMAs, semaphores and the barrier all lower."""
    mesh = Mesh(np.array(topo.devices[:4]), ("workers",))
    fn = jax.shard_map(
        lambda x: ring.ring_all_reduce(x[0], "workers")[None], mesh=mesh,
        in_specs=P("workers"), out_specs=P("workers"), check_vma=False)
    text = _compiled_text(
        fn, _f32(NamedSharding(mesh, P("workers")), 4, 1024, 128))
    assert "tpu_custom_call" in text and "/ring_all_reduce/" in text
