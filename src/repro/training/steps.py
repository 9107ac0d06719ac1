"""Train / serve step factories, including the paper's merge strategies.

Three granularities:

  * ``make_train_step``  — one synchronous SGD/Adam step; gradients are
    reduced across all DP axes implicitly by GSPMD (params replicated over
    DP => XLA inserts the all-reduce).  This is the STANDARD baseline.
  * ``make_window_step`` — one tau-step WINDOW with the paper's merge
    protocol across the ``merge_axis`` ('pod' on the multi-pod mesh):
      - AVERAGE      (paper eq. 3): w_srd = pmean(local w(tau))
      - DELTA        (paper eq. 8): w_srd = w0 - psum_i (w0 - w_i(tau))
      - ASYNC_DELTA  (paper eq. 9, TPU-idiomatic): the delta psum of window
        k-1 is applied at the END of window k, so the collective has no data
        dependency on window k's compute and XLA's latency-hiding scheduler
        overlaps it with the tau-step scan (the paper's lock-free reducer
        becomes a one-window-stale pipelined collective).
      - ALLREDUCE    : per-step psum over merge_axis inside the window
        (what the window buys you is measured against this).
    Implemented with shard_map manual over ``merge_axis`` and auto over the
    remaining mesh axes, so TP/FSDP sharding inside each pod is untouched.
  * ``make_serve_step`` / ``make_prefill_step`` — inference.
"""

from __future__ import annotations

import enum
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import comm
from repro.engine import merge as merge_lib
from repro.models.api import get_api
from repro.models.common import ModelConfig
from repro.optim.optimizers import Optimizer, clip_by_global_norm


class Merge(enum.Enum):
    ALLREDUCE = "allreduce"
    AVERAGE = "average"          # paper eq. (3) — the scheme that does NOT scale
    DELTA = "delta"              # paper eq. (8)
    ASYNC_DELTA = "async_delta"  # paper eq. (9), pipelined-collective form
    DELTA_SPARSE = "delta_sparse"  # eq. (8) + top-k/error-feedback compression


# ---------------------------------------------------------------------------
# plain synchronous step
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, optimizer: Optimizer,
                    *, clip: float = 1.0) -> Callable:
    api = get_api(cfg)

    def train_step(state: dict, batch: dict) -> tuple[dict, dict]:
        loss, grads = jax.value_and_grad(api.loss_fn)(state["params"], batch)
        grads, gnorm = clip_by_global_norm(grads, clip)
        params, opt_state = optimizer.update(
            grads, state["opt_state"], state["params"])
        new_state = {"params": params, "opt_state": opt_state,
                     "step": state["step"] + 1}
        return new_state, {"loss": loss, "grad_norm": gnorm}

    return train_step


def init_train_state(cfg: ModelConfig, optimizer: Optimizer,
                     key: jax.Array) -> dict:
    api = get_api(cfg)
    params = api.init(key)
    return {"params": params, "opt_state": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32)}


# ---------------------------------------------------------------------------
# paper-scheme window step
# ---------------------------------------------------------------------------

def make_window_step(cfg: ModelConfig, optimizer: Optimizer, mesh,
                     *, tau: int, merge: Merge, merge_axis: str = "pod",
                     clip: float = 1.0, compress_frac: float = 0.01,
                     transport: "comm.Transport | str | None" = None
                     ) -> Callable:
    """Returns window_step(state, batches) -> (state, metrics).

    ``batches``: pytree whose leaves have shape (tau, global_batch, ...).
    ``state`` additionally carries ``delta_prev`` for ASYNC_DELTA (init with
    zeros_like(params)) and ``residual`` for DELTA_SPARSE.

    All cross-pod collectives ride ``transport`` (a ``repro.comm`` name or
    instance; dense XLA by default) — the same merge implementations the VQ
    mesh engine uses, so the f32 wire convention and the wire-byte
    accounting are defined exactly once.  DELTA_SPARSE is the shared
    ``SparseDeltaMerge`` (top-k + error feedback over ``SparseTransport``).
    """
    api = get_api(cfg)
    axis = merge_axis
    if transport == "sparse":
        # the string spelling picks up this step's compression knob; an
        # explicit instance keeps its own frac (SparseDeltaMerge rejects a
        # conflicting pair)
        transport = comm.get_transport("sparse", frac=compress_frac)
    tsp = comm.get_transport(transport if transport is not None else "xla")
    if tsp.stateful and merge is Merge.DELTA:
        raise ValueError(
            "Merge.DELTA over a stateful transport would drop the "
            "error-feedback residual every window (the window step only "
            "carries residual state for DELTA_SPARSE) — use "
            "Merge.DELTA_SPARSE instead")
    # strategy objects are built once; the traced window body closes over
    # them (the merge tree algebra is shared with the VQ mesh engine)
    _average = merge_lib.AverageMerge(tsp)
    _delta = merge_lib.DeltaMerge(tsp)
    _async = merge_lib.AsyncDeltaMerge(tsp)
    _sparse = merge_lib.SparseDeltaMerge(
        tsp if isinstance(tsp, comm.SparseTransport) else None,
        frac=None if isinstance(tsp, comm.SparseTransport)
        else compress_frac)

    def _pmean_f32(tree, *, calls=1, tag="merge"):
        # the f32 wire convention (bf16 all-reduce promotion CHECK-fails in
        # XLA:CPU) lives in the transport layer, defined once for all users
        return tsp.all_reduce(tree, axis, op="mean", calls=calls,
                              tag=tag)[0]

    def local_step(state, batch):
        loss, grads = jax.value_and_grad(api.loss_fn)(state["params"], batch)
        if merge is Merge.ALLREDUCE:
            grads = _pmean_f32(grads, calls=tau)
        grads, gnorm = clip_by_global_norm(grads, clip)
        params, opt_state = optimizer.update(
            grads, state["opt_state"], state["params"])
        return ({"params": params, "opt_state": opt_state,
                 "step": state["step"] + 1}, loss)

    def window_body(state, batches):
        w0 = state["params"]
        inner = {k: state[k] for k in ("params", "opt_state", "step")}
        inner, losses = jax.lax.scan(local_step, inner, batches)
        wl = inner["params"]
        out = dict(inner)

        if merge is Merge.AVERAGE:
            out["params"], _ = _average(w0, wl, axis)
        elif merge is Merge.DELTA:
            out["params"], _ = _delta(w0, wl, axis)  # eq. (8)
        elif merge is Merge.DELTA_SPARSE:
            out["params"], out["residual"] = _sparse(
                w0, wl, axis, state["residual"])
        elif merge is Merge.ASYNC_DELTA:
            # merge LAST window's deltas — no data dependency on this
            # window's scan, so the collective overlaps with compute.
            out["params"], out["delta_prev"] = _async(
                w0, wl, axis, state["delta_prev"])
        else:  # ALLREDUCE merged per-step already
            out["params"] = wl
        if merge in (Merge.AVERAGE, Merge.DELTA):
            # keep local moments except under the barriered schemes, where
            # consensus moments keep workers exchangeable (DESIGN.md §3)
            out["opt_state"] = _pmean_f32(inner["opt_state"])
        if "delta_prev" in state and "delta_prev" not in out:
            out["delta_prev"] = state["delta_prev"]
        if "residual" in state and "residual" not in out:
            out["residual"] = state["residual"]
        return out, {"loss": jnp.mean(losses)}

    def window_step(state, batches):
        # specs: everything unsharded on merge_axis except the batch dim;
        # the TP/FSDP axes stay under GSPMD (manual axes = {merge_axis} only)
        def batch_spec(leaf):
            return P(None, axis, *([None] * (leaf.ndim - 2)))

        in_specs = (
            jax.tree.map(lambda _: P(), state),
            jax.tree.map(batch_spec, batches),
        )
        out_specs = (jax.tree.map(lambda _: P(), state),
                     {"loss": P()})
        fn = jax.shard_map(
            window_body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            axis_names=frozenset({axis}), check_vma=False)
        return fn(state, batches)

    return window_step


def init_window_state(cfg: ModelConfig, optimizer: Optimizer, key: jax.Array,
                      merge: Merge,
                      transport: "comm.Transport | str | None" = None
                      ) -> dict:
    """Seed the window-step state.  ``transport`` must match the one given
    to ``make_window_step``: a stateful transport widens ASYNC_DELTA's
    ``delta_prev`` to the joint {own, comm} carry the strategy expects."""
    state = init_train_state(cfg, optimizer, key)
    tsp = (comm.get_transport(transport) if transport is not None else None)
    if merge is Merge.ASYNC_DELTA:
        state["delta_prev"] = merge_lib.AsyncDeltaMerge(tsp).init_state(
            state["params"])
    if merge is Merge.DELTA_SPARSE:
        # the error-feedback residual IS the sparse transport's state
        state["residual"] = merge_lib.SparseDeltaMerge(
            tsp if isinstance(tsp, comm.SparseTransport) else None
        ).init_state(state["params"])
    return state


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def make_serve_step(cfg: ModelConfig, *, quantized: bool = False) -> Callable:
    """Decode step.  With ``quantized=True`` the params argument is the
    int8 tree from ``models.quantization.quantize_tree`` — weights are
    dequantized inside the jit (fused into the consuming matmuls), halving
    the HBM weight traffic that dominates decode (§Perf it.9)."""
    api = get_api(cfg)

    def serve_step(params: dict, cache: dict, tokens: jax.Array):
        if quantized:
            from repro.models import quantization
            params = quantization.dequantize_tree(params)
        return api.decode_step(params, cache, tokens)

    return serve_step


def make_prefill_step(cfg: ModelConfig, *, max_len: int | None = None
                      ) -> Callable:
    """Prefill = one forward over the prompt that ALSO fills the decode
    cache (per-layer K/V at [0, T); SSM conv tails + final state).
    Returns (last-position logits, cache ready for decode at cur_len=T)."""
    api = get_api(cfg)

    def prefill_step(params: dict, batch: dict):
        t = batch["tokens"].shape[1]
        return api.prefill(params, batch, max_len or t)

    return prefill_step
