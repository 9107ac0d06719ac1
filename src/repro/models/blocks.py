"""Block-level forward functions: GQA attention, SwiGLU MLP, MoE, Mamba2 SSD.

All functions are pure and take ``(cfg, params_leafdict, x, ...)``; they are
assembled into layer stacks (lax.scan over a leading L dim) by
``transformer.py`` / ``encdec.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import common
from repro.models.common import ModelConfig, rope


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _split_heads(x: jax.Array, n_heads: int) -> jax.Array:
    b, t, _ = x.shape
    return x.reshape(b, t, n_heads, -1)


def _pick_chunk(t: int, target: int = 512) -> int:
    for c in (target, 256, 128, 64, 32, 16, 8, 4, 2, 1):
        if c <= t and t % c == 0:
            return c
    return 1


def attention_train(cfg: ModelConfig, p: dict, x: jax.Array,
                    *, causal: bool = True,
                    window: jax.Array | int = 0,
                    q_chunk: int = 512, return_kv: bool = False):
    """Self-attention over a (B, T, D) block, chunked over query blocks.

    The (T, T) score matrix is never materialized: a ``lax.scan`` over query
    chunks computes exact softmax per chunk against the full K/V (Rabe &
    Staats-style memory-efficient attention — the pure-JAX analogue of a
    flash kernel; peak transient is (B, H, q_chunk, T) instead of
    (B, H, T, T)).  ``window`` > 0 masks to a sliding window (traced scalar
    ok, for per-layer hybrid schedules)."""
    b, t, _ = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (1, t))
    q = _split_heads(x @ p["wq"], hq)
    k = _split_heads(x @ p["wk"], hkv)
    v = _split_heads(x @ p["wv"], hkv)
    if cfg.rope_theta > 0:
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
    # NOTE: explicit q/k/v heads-over-'model' constraints were tried and
    # REFUTED (granite_34b collective term 1.28e12 -> 1.52e13 B: forcing the
    # layout fights GSPMD's propagation through RoPE/chunk-scan and inserts
    # per-layer resharding).  See EXPERIMENTS.md §Perf iteration 5.
    g = hq // hkv
    q = q.reshape(b, t, hkv, g, dh)

    c = _pick_chunk(t, q_chunk)
    nc = t // c
    qc = jnp.moveaxis(q.reshape(b, nc, c, hkv, g, dh), 1, 0)  # (nc,b,c,hkv,g,dh)
    scale = 1.0 / jnp.sqrt(jnp.asarray(dh, jnp.float32))
    w = jnp.asarray(window)
    kpos = jnp.arange(t, dtype=jnp.int32)

    def chunk_fn(i, qi):
        # qi: (b, c, hkv, g, dh); scores vs full K
        s = jnp.einsum("bthgd,bshd->bhgts", qi, k).astype(jnp.float32) * scale
        qpos = i * c + jnp.arange(c, dtype=jnp.int32)
        mask = jnp.ones((c, t), dtype=bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        mask &= (w <= 0) | (kpos[None, :] > qpos[:, None] - jnp.maximum(w, 1))
        s = jnp.where(mask[None, None, None], s, -1e30)
        probs = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        return jnp.einsum("bhgts,bshd->bthgd", probs, v)  # (b,c,hkv,g,dh)

    if nc == 1:
        out = chunk_fn(0, qc[0])[:, None]
        out = jnp.moveaxis(out, 1, 0)
    else:
        _, out = jax.lax.scan(
            lambda i, qi: (i + 1, chunk_fn(i, qi)),
            jnp.zeros((), jnp.int32), qc)            # (nc, b, c, hkv, g, dh)
    out = jnp.moveaxis(out, 0, 1).reshape(b, t, hq * dh)
    if return_kv:
        return out @ p["wo"], k, v
    return out @ p["wo"]


def attention_decode(cfg: ModelConfig, p: dict, x: jax.Array,
                     k_cache: jax.Array, v_cache: jax.Array,
                     cur_len: jax.Array, *, window: jax.Array | int = 0
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode.  x: (B, 1, D); caches: (B, S, Hkv, Dh).

    Returns (out (B,1,D), new_k_cache, new_v_cache).  Attends to positions
    [0, cur_len]; the new token is written at index cur_len.
    """
    b, _, _ = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    s = k_cache.shape[1]
    pos = jnp.full((b, 1), cur_len, jnp.int32)
    q = _split_heads(x @ p["wq"], hq)
    k = _split_heads(x @ p["wk"], hkv)
    v = _split_heads(x @ p["wv"], hkv)
    if cfg.rope_theta > 0:
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k.astype(k_cache.dtype), (0, cur_len, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v.astype(v_cache.dtype), (0, cur_len, 0, 0))

    g = hq // hkv
    q = q.reshape(b, 1, hkv, g, dh)
    scores = jnp.einsum("bthgd,bshd->bhgts", q, k_cache).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.asarray(dh, jnp.float32))
    kpos = jnp.arange(s)[None, None, None, None, :]
    mask = kpos <= cur_len
    w = jnp.asarray(window)
    mask &= (w <= 0) | (kpos > cur_len - jnp.maximum(w, 1))
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhgts,bshd->bthgd", probs, v_cache).reshape(b, 1, hq * dh)
    return out @ p["wo"], k_cache, v_cache


def init_attention(cfg: ModelConfig, key: jax.Array, n_layers: int) -> dict:
    hq, hkv, dh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    ks = jax.random.split(key, 4)
    shp = lambda *s: (n_layers, *s)
    return {
        "wq": common.init_dense(ks[0], shp(d, hq * dh), cfg.dtype),
        "wk": common.init_dense(ks[1], shp(d, hkv * dh), cfg.dtype),
        "wv": common.init_dense(ks[2], shp(d, hkv * dh), cfg.dtype),
        "wo": common.init_dense(ks[3], shp(hq * dh, d), cfg.dtype),
    }


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def swiglu(p: dict, x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def gelu_mlp(p: dict, x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x @ p["w_up"]) @ p["w_down"]


def init_swiglu(cfg: ModelConfig, key: jax.Array, n_layers: int) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": common.init_dense(ks[0], (n_layers, d, f), cfg.dtype),
        "w_up": common.init_dense(ks[1], (n_layers, d, f), cfg.dtype),
        "w_down": common.init_dense(ks[2], (n_layers, f, d), cfg.dtype),
    }


# ---------------------------------------------------------------------------
# MoE (top-k routing, capacity-bounded, sort-free scatter dispatch)
# ---------------------------------------------------------------------------

def _moe_shard(x: jax.Array, spec_dims) -> jax.Array:
    """Sharding constraint helper for MoE internals (no-op without a mesh)."""
    mesh = common.get_run_options().mesh
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = tuple(a for a in ("pod", "data") if a in sizes)
    dp_total = 1
    for a in dp:
        dp_total *= sizes[a]
    spec = []
    for dim, kind in zip(x.shape, spec_dims):
        if kind == "batch" and dp and dim % dp_total == 0:
            spec.append(dp)
        elif kind == "expert" and "model" in sizes \
                and dim % sizes["model"] == 0:
            spec.append("model")
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))


def _moe_route(cfg: ModelConfig, router: jax.Array, x: jax.Array):
    """Shared routing: per-row ranks and capacity mask.

    Returns (gates (B,T,k), unit_e (B,U), unit_pos (B,U), keep (B,U), cap).
    """
    b, t, _ = x.shape
    e, k = cfg.n_experts, cfg.top_k
    u = t * k
    logits = (x @ router).astype(jnp.float32)                   # (B, T, E)
    gates, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), k)   # (B, T, k)
    gates = gates / jnp.sum(gates, -1, keepdims=True)
    cap = int(cfg.capacity_factor * t * k / e) or 1
    unit_e = idx.reshape(b, u)
    onehot = jax.nn.one_hot(unit_e, e, dtype=jnp.int32)
    pos = (jnp.cumsum(onehot, axis=1) - 1) * onehot             # per-row rank
    unit_pos = jnp.sum(pos, axis=-1)
    keep = unit_pos < cap
    return gates, unit_e, jnp.where(keep, unit_pos, 0), keep, cap


def moe_apply_ep(cfg: ModelConfig, p: dict, x: jax.Array,
                 mesh) -> jax.Array:
    """Expert-parallel MoE via shard_map manual over the TP axis.

    Each 'model' shard owns E/tp experts.  Routing is computed redundantly
    per shard (router is replicated, cheap); each shard scatters only the
    units destined to ITS experts, runs its expert FFNs, applies the
    gate-weighted combine LOCALLY, and contributes a partial (B, T, D) that
    is psum'd once over 'model' — k*8x fewer reduced bytes than psumming the
    per-unit (B, T*k, D) gather, and no (B,U,D) all-gathers (EXPERIMENTS.md
    §Perf iteration 2).
    """
    from jax.sharding import PartitionSpec as P

    e, k = cfg.n_experts, cfg.top_k
    tp = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    e_loc = e // tp

    def body(x_in, router_in, wg, wu, wd):
        # f32 at the shard_map boundary: the backward pass psums the
        # cotangents of the replicated-in operands over 'model', and
        # XLA:CPU's bf16 all-reduce promotion CHECK-fails (real TPUs do
        # bf16 reductions natively; this boundary is the CPU-safe form).
        x_r = x_in.astype(x.dtype)
        router = router_in.astype(x.dtype)
        b, t, d = x_r.shape
        gates, unit_e, unit_pos, keep, cap = _moe_route(cfg, router, x_r)
        shard = jax.lax.axis_index("model")
        lo = shard * e_loc
        mine = keep & (unit_e >= lo) & (unit_e < lo + e_loc)
        e_local = jnp.where(mine, unit_e - lo, 0)
        pos = jnp.where(mine, unit_pos, 0)
        xu = jnp.repeat(x_r, k, axis=1)
        xu = jnp.where(mine[..., None], xu, 0)

        def row_scatter(xu_r, e_r, p_r):
            return jnp.zeros((e_loc, cap, d), x_r.dtype).at[e_r, p_r].add(xu_r)

        buf = jax.vmap(row_scatter)(xu, e_local, pos)           # (B,El,C,D)
        h = jax.nn.silu(jnp.einsum("becd,edf->becf", buf, wg)) \
            * jnp.einsum("becd,edf->becf", buf, wu)
        yb = jnp.einsum("becf,efd->becd", h, wd)                # (B,El,C,D)

        def row_gather(yb_r, e_r, p_r):
            return yb_r[e_r, p_r]

        yu = jax.vmap(row_gather)(yb, e_local, pos)
        yu = yu * mine[..., None]
        y_part = jnp.sum(yu.reshape(b, t, k, d)
                         * gates[..., None].astype(yu.dtype), axis=2)
        return jax.lax.psum(y_part.astype(jnp.float32), "model")

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P("model"), P("model"), P("model")),
        out_specs=P(),
        axis_names=frozenset({"model"}), check_vma=False)
    return fn(x.astype(jnp.float32), p["router"].astype(jnp.float32),
              p["w_gate"], p["w_up"], p["w_down"]).astype(x.dtype)


def moe_apply(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    """Top-k MoE over a (B, T, D) block — locality-preserving dispatch.

    Routing ranks are computed PER ROW (capacity = capacity_factor*T*k/E per
    sequence), so the rank cumsum never crosses the data-sharded batch dim
    and the scatter into the (B, E, C, D) buffer is local to each data
    shard.  Experts live on the TP axis: the buffer is constrained to
    (B:data, E:model, C, D); the gather-back from the E-sharded buffer
    lowers to mask + psum over 'model' — the same row-parallel reduce as a
    Megatron MLP, instead of the all-to-all storm a global-rank dispatch
    produces (52.6s -> see EXPERIMENTS.md §Perf for the measured drop).
    """
    # moe_ep: shard_map expert parallelism — measured WORSE than the vmap
    # dispatch under XLA:CPU GSPMD (nested manual-model + auto-data causes
    # per-layer (B,U,D) f32 all-gathers; see EXPERIMENTS.md §Perf it.3),
    # so it's opt-in for future re-evaluation on real TPU toolchains.
    opts = common.get_run_options()
    mesh = opts.mesh
    if (getattr(opts, "moe_ep", False)
            and mesh is not None and "model" in mesh.axis_names
            and cfg.n_experts
            % dict(zip(mesh.axis_names, mesh.devices.shape))["model"] == 0):
        return moe_apply_ep(cfg, p, x, mesh)

    b, t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    gates, unit_e, unit_pos, keep, cap = _moe_route(cfg, p["router"], x)
    xu = jnp.repeat(x, k, axis=1)                               # (B, U, D)
    xu = jnp.where(keep[..., None], xu, 0)

    # vmap over batch so B is a true scatter/gather BATCH dim — XLA then
    # partitions B on 'data' and handles the E-sharded dim by index-masking
    # (+ psum on the gather), instead of replicating the whole buffer.
    def row_scatter(xu_r, e_r, p_r):
        return jnp.zeros((e, cap, d), x.dtype).at[e_r, p_r].add(xu_r)

    buf = jax.vmap(row_scatter)(xu, unit_e, unit_pos)
    buf = _moe_shard(buf, ("batch", "expert", None, None))      # (B,E,C,D)

    h = jax.nn.silu(jnp.einsum("becd,edf->becf", buf, p["w_gate"])) \
        * jnp.einsum("becd,edf->becf", buf, p["w_up"])
    yb = jnp.einsum("becf,efd->becd", h, p["w_down"])
    yb = _moe_shard(yb, ("batch", "expert", None, None))        # (B,E,C,D)

    def row_gather(yb_r, e_r, p_r):
        return yb_r[e_r, p_r]

    yu = jax.vmap(row_gather)(yb, unit_e, unit_pos)             # (B, U, D)
    yu = yu * keep[..., None]
    y = jnp.sum(yu.reshape(b, t, k, d)
                * gates[..., None].astype(yu.dtype), axis=2)
    return y.astype(x.dtype)


def init_moe(cfg: ModelConfig, key: jax.Array, n_layers: int) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    return {
        "router": common.init_dense(ks[0], (n_layers, d, e), cfg.dtype),
        "w_gate": common.init_dense(ks[1], (n_layers, e, d, f), cfg.dtype),
        "w_up": common.init_dense(ks[2], (n_layers, e, d, f), cfg.dtype),
        "w_down": common.init_dense(ks[3], (n_layers, e, f, d), cfg.dtype),
    }


def moe_aux_loss(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    """Load-balancing auxiliary loss (Switch-style) for one block."""
    logits = (x.reshape(-1, cfg.d_model) @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    top1 = jnp.argmax(probs, -1)
    frac = jnp.mean(jax.nn.one_hot(top1, cfg.n_experts), axis=0)
    imp = jnp.mean(probs, axis=0)
    return cfg.n_experts * jnp.sum(frac * imp)


# ---------------------------------------------------------------------------
# Mamba2 (SSD — state space duality, chunked scan)
# ---------------------------------------------------------------------------

def _causal_conv(xbc: jax.Array, conv_w: jax.Array) -> jax.Array:
    """Depthwise causal conv.  xbc: (B, T, C), conv_w: (W, C)."""
    w = conv_w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (w - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xbc.shape[1]] * conv_w[i] for i in range(w))
    return jax.nn.silu(out)


def _segsum(logd: jax.Array) -> jax.Array:
    """(..., Q) -> (..., Q, Q) lower-triangular pairwise sums of log-decays:
    out[i, j] = sum_{k=j+1..i} logd[k] for i >= j, -inf otherwise."""
    q = logd.shape[-1]
    cs = jnp.cumsum(logd, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]  # sum_{k=j+1..i}
    i = jnp.arange(q)
    mask = i[:, None] >= i[None, :]
    return jnp.where(mask, diff, -jnp.inf)


def ssd_train(cfg: ModelConfig, xh: jax.Array, dt: jax.Array, A: jax.Array,
              B: jax.Array, C: jax.Array, *, chunk: int = 128
              ) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD forward (Mamba2 alg. 1, G=1 group).

    xh: (b, T, H, P) head-split inputs; dt: (b, T, H) positive step sizes;
    A: (H,) negative decay rates; B, C: (b, T, N).
    Returns (y: (b, T, H, P), final_state: (b, H, P, N)) — the final state
    feeds decode after a prefill.
    """
    b, t, h, pdim = xh.shape
    q = min(chunk, t)
    assert t % q == 0, "seq_len must divide the SSD chunk"
    nc = t // q
    # reshape into chunks
    xc = xh.reshape(b, nc, q, h, pdim)
    dtc = dt.reshape(b, nc, q, h)
    Bc = B.reshape(b, nc, q, -1)
    Cc = C.reshape(b, nc, q, -1)
    logd = dtc * A  # (b, nc, q, h) log-decay per step (A < 0)

    # ---- intra-chunk (quadratic attention-like) term ----
    L = _segsum(jnp.moveaxis(logd, -1, -2))            # (b, nc, h, q, q)
    G = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)          # (b, nc, q, q)
    M = G[:, :, None] * jnp.exp(L)                     # (b, nc, h, q, q)
    M = M * jnp.moveaxis(dtc, -1, -2)[..., None, :]    # weight by dt_j
    y_intra = jnp.einsum("bchij,bcjhp->bcihp", M.astype(xh.dtype), xc)

    # ---- chunk-final states and inter-chunk recurrence ----
    cum = jnp.cumsum(logd, axis=2)                     # (b, nc, q, h)
    total = cum[:, :, -1]                              # (b, nc, h)
    decay_to_end = jnp.exp(total[:, :, None] - cum)    # (b, nc, q, h)
    # state contribution of chunk c: sum_j decay_to_end_j * dt_j * B_j x_j
    Sc = jnp.einsum("bcjh,bcjn,bcjhp->bchpn",
                    (decay_to_end * dtc).astype(jnp.float32),
                    Bc.astype(jnp.float32), xc.astype(jnp.float32))

    def scan_body(s_prev, inp):
        sc, tot = inp  # (b,h,p,n), (b,h)
        s_new = jnp.exp(tot)[..., None, None] * s_prev + sc
        return s_new, s_prev

    s0 = jnp.zeros((b, h, pdim, Sc.shape[-1]), jnp.float32)
    s_final, s_prevs = jax.lax.scan(
        scan_body, s0,
        (jnp.moveaxis(Sc, 1, 0), jnp.moveaxis(total, 1, 0)))
    s_prevs = jnp.moveaxis(s_prevs, 0, 1)              # (b, nc, h, p, n)

    decay_from_start = jnp.exp(cum)                    # (b, nc, q, h)
    y_inter = jnp.einsum("bcin,bchpn,bcih->bcihp",
                         Cc.astype(jnp.float32), s_prevs, decay_from_start)
    y = y_intra + y_inter.astype(xh.dtype)
    return y.reshape(b, t, h, pdim), s_final


def mamba_train(cfg: ModelConfig, p: dict, x: jax.Array,
                *, return_state: bool = False):
    """Full Mamba2 mixer over (B, T, D).

    Projections are stored SEPARATELY (in_z / in_x / in_bc / in_dt rather
    than one fused in_proj) so each can carry its own TP sharding without
    slicing across stream boundaries on a sharded dim.  With
    ``return_state`` also returns (conv_x_tail, conv_bc_tail, ssm_state)
    to seed decode after a prefill."""
    b, t, _ = x.shape
    di, n, h, pdim = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_headdim
    z = x @ p["in_z"]                       # (B, T, di)
    xs_raw = x @ p["in_x"]                  # (B, T, di) pre-conv
    bc_raw = x @ p["in_bc"]                 # (B, T, 2n)
    xin = _causal_conv(xs_raw, p["conv_x"])             # (B, T, di)
    bc = _causal_conv(bc_raw, p["conv_bc"])             # (B, T, 2n)
    B, C = jnp.split(bc, 2, axis=-1)
    dt = jax.nn.softplus((x @ p["in_dt"]).astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    xh = xin.reshape(b, t, h, pdim)
    y, s_final = ssd_train(cfg, xh, dt, A, B, C)
    y = y + p["D"].astype(xh.dtype)[None, None, :, None] * xh
    y = y.reshape(b, t, di) * jax.nn.silu(z)
    out = y @ p["out_proj"]
    if return_state:
        w = cfg.ssm_conv
        pad_x = jnp.pad(xs_raw, ((0, 0), (w - 1, 0), (0, 0)))
        pad_bc = jnp.pad(bc_raw, ((0, 0), (w - 1, 0), (0, 0)))
        return out, pad_x[:, t:t + w - 1], pad_bc[:, t:t + w - 1], s_final
    return out


def mamba_decode(cfg: ModelConfig, p: dict, x: jax.Array,
                 conv_x_st: jax.Array, conv_bc_st: jax.Array,
                 ssm_state: jax.Array
                 ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One-token Mamba2 step.  x: (B, 1, D); conv_x_st: (B, W-1, di);
    conv_bc_st: (B, W-1, 2n); ssm_state: (B, H, P, N)."""
    b = x.shape[0]
    di, n, h, pdim = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_headdim
    z = (x @ p["in_z"])[:, 0]                              # (B, di)
    xs = x @ p["in_x"]                                     # (B, 1, di)
    bcs = x @ p["in_bc"]                                   # (B, 1, 2n)
    hist_x = jnp.concatenate([conv_x_st, xs], axis=1)      # (B, W, di)
    hist_bc = jnp.concatenate([conv_bc_st, bcs], axis=1)
    xin = jax.nn.silu(jnp.sum(hist_x * p["conv_x"][None], axis=1))   # (B, di)
    bc = jax.nn.silu(jnp.sum(hist_bc * p["conv_bc"][None], axis=1))  # (B, 2n)
    B, C = jnp.split(bc, 2, axis=-1)
    dt1 = jax.nn.softplus(
        (x @ p["in_dt"])[:, 0].astype(jnp.float32) + p["dt_bias"])   # (B, H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    dA = jnp.exp(dt1 * A)                                  # (B, H)
    xh = xin.reshape(b, h, pdim)
    ssm_state = (dA[..., None, None] * ssm_state
                 + jnp.einsum("bh,bn,bhp->bhpn",
                              dt1, B.astype(jnp.float32),
                              xh.astype(jnp.float32)))
    y = jnp.einsum("bhpn,bn->bhp", ssm_state, C.astype(jnp.float32))
    y = y.astype(x.dtype) + p["D"].astype(x.dtype)[None, :, None] * xh
    y = (y.reshape(b, di) * jax.nn.silu(z))[:, None, :]
    return y @ p["out_proj"], hist_x[:, 1:], hist_bc[:, 1:], ssm_state


def init_mamba(cfg: ModelConfig, key: jax.Array, n_layers: int) -> dict:
    d, di, n, h = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    ks = jax.random.split(key, 7)
    shp = lambda *s: (n_layers, *s)
    return {
        "in_z": common.init_dense(ks[0], shp(d, di), cfg.dtype),
        "in_x": common.init_dense(ks[1], shp(d, di), cfg.dtype),
        "in_bc": common.init_dense(ks[2], shp(d, 2 * n), cfg.dtype),
        "in_dt": common.init_dense(ks[3], shp(d, h), cfg.dtype),
        "conv_x": common.init_dense(ks[4], shp(cfg.ssm_conv, di), cfg.dtype,
                                    scale=0.5),
        "conv_bc": common.init_dense(ks[5], shp(cfg.ssm_conv, 2 * n),
                                     cfg.dtype, scale=0.5),
        "out_proj": common.init_dense(ks[6], shp(di, d), cfg.dtype),
        "A_log": jnp.zeros((n_layers, h), jnp.float32),
        "D": jnp.ones((n_layers, h), jnp.float32),
        "dt_bias": jnp.full((n_layers, h), -1.0, jnp.float32),
    }
