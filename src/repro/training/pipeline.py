"""GPipe-style pipeline parallelism over the ``pod`` axis (SPMD form).

The layer stack is split into S = |pod| stages; stage s holds layers
[s*L/S, (s+1)*L/S) — the stacked block leaves are simply sharded over 'pod'
on their leading L dim, so PP is a STORAGE layout plus this schedule, and
composes with the TP/FSDP sharding of the other axes (auto under the
shard_map).

Schedule: classic GPipe fill-drain over ``n_micro`` microbatches in
``n_micro + S - 1`` ticks.  Every tick each stage (i) picks its input — a
fresh microbatch on stage 0, the neighbor's output elsewhere — (ii) runs its
local layers (lax.scan), (iii) ``collective_permute``s the activation to the
next stage.  Backward falls out of jax.grad: the vjp of collective_permute
is the reverse permute, giving the standard backward-pipeline automatically.

Bubble fraction = (S-1)/(n_micro+S-1); the dry-run lowering
(EXPERIMENTS.md §Perf it.10) shows the activation-permute bytes replacing
the FSDP/TP weight traffic of the non-PP layout.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import comm
from repro.models import blocks
from repro.models.common import ModelConfig, rms_norm

# loss-reduction collective rides the default dense transport so the comm
# grep stays clean: no raw collective call sites outside repro.comm
_COMM = comm.get_transport("xla")


def stage_param_specs(cfg: ModelConfig, base_specs: dict) -> dict:
    """PP layout: block leaves add 'pod' on the leading (layer) dim."""
    out = dict(base_specs)
    out["blocks"] = {
        name: P("pod", *spec) if len(spec) >= 0 else spec
        for name, spec in base_specs["blocks"].items()
    }

    def fix(name, spec):
        # spec for (L, ...) leaf: replace leading None with 'pod'
        rest = tuple(spec)[1:]
        return P("pod", *rest)

    out["blocks"] = {name: fix(name, spec)
                     for name, spec in base_specs["blocks"].items()}
    return out


def make_pp_loss_fn(cfg: ModelConfig, mesh: Mesh, *, n_micro: int
                    ) -> Callable:
    """Pipelined loss for the dense decoder family.

    params: the usual pytree with block leaves sharded P('pod', ...) on L.
    batch: {'tokens','labels'} with batch dim sharded over 'data' (auto).
    Requires cfg.family == 'dense' and n_layers % S == 0.
    """
    assert cfg.family == "dense", "PP demo covers the dense family"
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    s_stages = sizes["pod"]
    assert cfg.n_layers % s_stages == 0

    def body(params, batch):
        stage = jax.lax.axis_index("pod")
        blk = params["blocks"]          # local (L/S, ...) slices
        tokens, labels = batch["tokens"], batch["labels"]
        b, t = tokens.shape
        assert b % n_micro == 0
        mb = b // n_micro

        # replicated-in leaves ride the shard_map boundary in f32: their
        # backward cotangents psum over 'pod', and XLA:CPU's bf16
        # all-reduce promotion CHECK-fails (same workaround as moe_apply_ep)
        embed = params["embed"].astype(cfg.dtype)
        x_all = jnp.take(embed, tokens, axis=0)            # (B, T, D)
        micro = x_all.reshape(n_micro, mb, t, -1)

        def run_stage(x):
            def scan_fn(carry, p):
                return blocks_apply(p, carry), None

            def blocks_apply(p, x):
                x = x + blocks.attention_train(
                    cfg, p, rms_norm(x, p["attn_norm"], cfg.norm_eps))
                x = x + blocks.swiglu(
                    {k: p[k] for k in ("w_gate", "w_up", "w_down")},
                    rms_norm(x, p["mlp_norm"], cfg.norm_eps))
                return x

            body_fn = jax.checkpoint(
                blocks_apply,
                policy=jax.checkpoint_policies.nothing_saveable)
            x, _ = jax.lax.scan(
                lambda c, p: (body_fn(p, c), None), x, blk)
            return x

        n_ticks = n_micro + s_stages - 1
        perm = [(i, (i + 1) % s_stages) for i in range(s_stages)]

        def tick_fn(carry, i):
            recv, outs = carry
            take = jnp.clip(i, 0, n_micro - 1)
            fresh = jax.lax.dynamic_index_in_dim(
                micro, take, axis=0, keepdims=False)
            x_in = jnp.where(stage == 0, fresh, recv)
            y = run_stage(x_in)
            sent = jax.lax.ppermute(y, "pod", perm)
            # last stage's output for microbatch (i - S + 1) is y at tick i
            out_idx = jnp.clip(i - (s_stages - 1), 0, n_micro - 1)
            valid = (i >= s_stages - 1)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, out_idx, axis=0),
                lambda o: o, outs)
            return (sent, outs), None

        outs0 = jnp.zeros_like(micro)
        (_, outs), _ = jax.lax.scan(
            tick_fn, (jnp.zeros_like(micro[0]), outs0),
            jnp.arange(n_ticks))

        # only the LAST stage holds real activations: every stage computes
        # the (cheap relative to the stack) loss head on ITS buffer and a
        # masked psum selects the real one — no permutation needed.
        last = s_stages - 1
        x = outs.reshape(b, t, -1)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = embed.T if cfg.tie_embeddings \
            else params["lm_head"].astype(cfg.dtype)
        logits = (x @ head).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, labels[..., None], axis=-1)[..., 0]
        ce = jnp.mean(logz - gold)
        # only the last stage's ce is real; the masked cross-stage sum
        # selects it (scalar — negligible wire, tagged as instrumentation)
        return _COMM.all_reduce(jnp.where(stage == last, ce, 0.0), "pod",
                                op="sum", tag="eval")[0]

    blocks_spec = {  # leading L dim manual over 'pod'
        name: P("pod") for name in
        ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
         "attn_norm", "mlp_norm")
    }
    param_specs = {
        "embed": P(), "final_norm": P(), "blocks": blocks_spec,
    }
    # lm_head present when embeddings untied
    def loss(params, batch):
        pspec = dict(param_specs)
        params = dict(params)
        params["embed"] = params["embed"].astype(jnp.float32)
        if "lm_head" in params:
            pspec["lm_head"] = P()
            params["lm_head"] = params["lm_head"].astype(jnp.float32)
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(pspec, {"tokens": P(), "labels": P()}),
            out_specs=P(),
            axis_names=frozenset({"pod"}), check_vma=False)
        return fn(params, batch)

    return loss
