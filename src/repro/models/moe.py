"""Mixture-of-Experts FFN with capacity-based sort-free dispatch.

Top-k routing with a static per-expert capacity C = ceil(T·K/E · cf):
each (token, k) slot computes its position within its expert via a
cumulative count and is scattered into an (E·C, d) buffer; expert FFNs
run as one batched einsum over the expert-sharded buffer; results gather
back weighted by the (renormalized) gates.  Overflowing tokens drop
(standard capacity semantics) — the residual stream carries them.

Under pjit the buffer is sharded (E over 'model', i.e. expert parallel);
the scatter/gather lower to all-to-alls on TPU.  An aux load-balance
loss (Switch-style) and router z-loss are returned for the train step.

This is also the one honest touch point with the paper's scheduling
story: tokens are "tasks", the router's gate is the workload estimate,
and capacity is the cut-off that keeps any single expert (device) from
becoming the bottleneck straggler.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import dense_init
from .sharding import constrain

__all__ = ["init_moe", "moe_ffn"]


def init_moe(key, d_model, n_experts, moe_d_ff, n_shared, *, dtype):
    ks = jax.random.split(key, 7)
    p = dict(
        router=dense_init(ks[0], (d_model, n_experts), jnp.float32),
        w_gate=dense_init(ks[1], (n_experts, d_model, moe_d_ff), dtype),
        w_up=dense_init(ks[2], (n_experts, d_model, moe_d_ff), dtype),
        w_down=dense_init(ks[3], (n_experts, moe_d_ff, d_model), dtype),
    )
    if n_shared:
        f = moe_d_ff * n_shared
        p.update(
            sh_gate=dense_init(ks[4], (d_model, f), dtype),
            sh_up=dense_init(ks[5], (d_model, f), dtype),
            sh_down=dense_init(ks[6], (f, d_model), dtype),
        )
    return p


def _grouped_moe(p, xf, *, top_k, capacity_factor):
    """Switch-style grouped-local dispatch (§Perf round 3).

    The global-cumsum dispatch scatters every dp shard's tokens into ONE
    shared (E·C, d) buffer — GSPMD merges the per-shard partials with an
    all-reduce of the whole capacity buffer every layer (measured 10.5 TB
    per chip on qwen3-moe×train_4k).  Grouped dispatch gives each data
    shard its own capacity slice: positions are a per-group cumsum, the
    scatter/gather are shard-local, and expert weights live EP-only
    (E over 'model', replicated over 'data'), so the expert einsums are
    collective-free; only the token-sized reshard crosses the mesh.
    """
    from .sharding import get_mesh_ctx

    t, d = xf.shape
    e = p["router"].shape[1]
    ctx = get_mesh_ctx()
    g_sz = 1
    if ctx is not None and ctx.dp:
        g_sz = ctx.size(ctx.dp if len(ctx.dp) > 1 else ctx.dp[0])
    if t % g_sz:
        g_sz = 1
    tg = t // g_sz
    xg = constrain(xf.reshape(g_sz, tg, d), ("dp", None, None))

    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, top_k)               # (G,Tg,K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    cap = int(max(1, round(tg * top_k / e * capacity_factor)))
    flat_e = idx.transpose(0, 2, 1).reshape(g_sz, -1)          # (G, K*Tg)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=1) - onehot                  # per-group
    my_pos = jnp.take_along_axis(pos, flat_e[..., None], axis=2)[..., 0]
    keep = my_pos < cap
    slot = jnp.where(keep, flat_e * cap + my_pos, e * cap)

    xk = jnp.tile(xg, (1, top_k, 1))                           # (G,K*Tg,d)
    gi = jnp.arange(g_sz)[:, None]
    buf = jnp.zeros((g_sz, e * cap + 1, d), xf.dtype).at[gi, slot].add(xk)
    buf = buf[:, :-1].reshape(g_sz, e, cap, d)
    buf = constrain(buf, ("dp", "tp", None, None))

    gg = jnp.einsum("gecd,edf->gecf", buf, p["w_gate"])
    uu = jnp.einsum("gecd,edf->gecf", buf, p["w_up"])
    y = jnp.einsum("gecf,efd->gecd", jax.nn.silu(gg) * uu, p["w_down"])
    y = constrain(y, ("dp", "tp", None, None))

    yf = y.reshape(g_sz, e * cap, d)
    yf = jnp.concatenate([yf, jnp.zeros((g_sz, 1, d), y.dtype)], axis=1)
    gathered = yf[gi, slot]                                    # (G,K*Tg,d)
    w = (gate_vals.transpose(0, 2, 1).reshape(g_sz, -1) * keep).astype(xf.dtype)
    out = (gathered * w[..., None]).reshape(g_sz, top_k, tg, d).sum(1)
    out = out.reshape(t, d)

    frac_tokens = jnp.mean(
        jax.nn.one_hot(idx[..., 0].reshape(-1), e, dtype=jnp.float32), 0
    )
    frac_probs = probs.reshape(-1, e).mean(0)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    zloss = jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
    return out, dict(load_balance=aux, z_loss=zloss)


def _manual_moe(p, xf, *, top_k, capacity_factor):
    """Manual-collective EP dispatch via shard_map (§Perf round 5).

    Every GSPMD-annotation attempt (rounds 2–4) was refuted: the SPMD
    partitioner resolves the capacity-buffer redistribution into
    whole-buffer all-gathers/all-reduces (measured 12–78 TB/chip wire
    bytes).  This path takes the collectives out of GSPMD's hands:

    * tokens are dp-sharded, **replicated over 'model'**, so every model
      shard computes the same routing locally (no dispatch communication
      at all — the paper-scheduler analogy: every worker sees the same
      task list and claims its own slice);
    * each model shard owns E/tp experts (EP-only weights) and builds
      the capacity buffer for *its* experts from *its* dp-local tokens —
      a purely local scatter;
    * expert FFNs run local; the only cross-shard traffic is ONE psum
      over 'model' of the token-sized combine (+ the usual grad sync).

    Requires a mesh context; falls back to "auto" without one.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from .sharding import get_mesh_ctx

    ctx = get_mesh_ctx()
    t, d = xf.shape
    e = p["router"].shape[1]
    if ctx is None or ctx.tp is None or e % ctx.size(ctx.tp):
        return None  # caller falls back
    dp_axes = ctx.dp if len(ctx.dp) > 1 else (ctx.dp[0] if ctx.dp else None)
    dp_sz = ctx.size(dp_axes)
    tp = ctx.tp
    tp_sz = ctx.size(tp)
    e_local = e // tp_sz
    if t % dp_sz:
        return None
    t_local = t // dp_sz
    cap = int(max(1, round(t_local * top_k / e * capacity_factor)))

    def local_fn(x_loc, router, wg, wu, wd):
        tl = x_loc.shape[0]
        logits = jnp.einsum("td,de->te", x_loc.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, idx = jax.lax.top_k(probs, top_k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9
        )
        flat_e = idx.T.reshape(-1)                       # (K*tl,)
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - onehot
        my_pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
        keep = my_pos < cap
        m_idx = jax.lax.axis_index(tp)
        mine = (flat_e // e_local) == m_idx              # expert on this shard
        le = flat_e % e_local
        slot = jnp.where(keep & mine, le * cap + my_pos, e_local * cap)
        xk = jnp.tile(x_loc, (top_k, 1))
        buf = jnp.zeros((e_local * cap + 1, d), x_loc.dtype).at[slot].add(xk)
        buf = buf[:-1].reshape(e_local, cap, d)
        g = jnp.einsum("ecd,edf->ecf", buf, wg)
        u = jnp.einsum("ecd,edf->ecf", buf, wu)
        y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd)
        yf = jnp.concatenate(
            [y.reshape(e_local * cap, d), jnp.zeros((1, d), y.dtype)]
        )
        gathered = yf[slot]                              # zeros off-shard
        w = (gate_vals.T.reshape(-1) * keep).astype(x_loc.dtype)
        out = (gathered * w[:, None]).reshape(top_k, tl, d).sum(0)
        out = jax.lax.psum(out, tp)                      # combine experts
        frac_tokens = jnp.mean(
            jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), 0
        )
        aux = e * jnp.sum(frac_tokens * probs.mean(0))
        zloss = jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
        if dp_axes is not None:
            aux = jax.lax.pmean(aux, dp_axes)
            zloss = jax.lax.pmean(zloss, dp_axes)
        return out, aux, zloss

    out, aux, zloss = shard_map(
        local_fn,
        mesh=ctx.mesh,
        in_specs=(P(dp_axes, None), P(), P(tp, None, None),
                  P(tp, None, None), P(tp, None, None)),
        out_specs=(P(dp_axes, None), P(), P()),
        check_vma=False,
    )(xf, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return out, dict(load_balance=aux, z_loss=zloss)


def moe_ffn(p, x, *, top_k, capacity_factor=1.25, dispatch_sharding="auto"):
    """x: (B, S, d) → (y, aux) with aux = load-balance + z losses."""
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    if dispatch_sharding == "manual":
        res = _manual_moe(p, xf, top_k=top_k, capacity_factor=capacity_factor)
        if res is not None:
            out, aux = res
            if "sh_gate" in p:
                gs = jnp.einsum("td,df->tf", xf, p["sh_gate"])
                us = jnp.einsum("td,df->tf", xf, p["sh_up"])
                out = out + jnp.einsum("tf,fd->td", jax.nn.silu(gs) * us,
                                       p["sh_down"])
            return out.reshape(b, s, d), aux
        dispatch_sharding = "auto"  # no mesh → fall through
    if dispatch_sharding == "grouped":
        out, aux = _grouped_moe(p, xf, top_k=top_k,
                                capacity_factor=capacity_factor)
        if "sh_gate" in p:
            gs = jnp.einsum("td,df->tf", xf, p["sh_gate"])
            us = jnp.einsum("td,df->tf", xf, p["sh_up"])
            out = out + jnp.einsum("tf,fd->td", jax.nn.silu(gs) * us,
                                   p["sh_down"])
        return out.reshape(b, s, d), aux
    if dispatch_sharding == "tokens_dp":
        # untangle SP: token dim purely data-parallel, d replicated — the
        # dispatch scatter/gather become dp-local and the expert einsum
        # contracts an UNsharded d (kills the per-layer all-reduce; the
        # token↔expert movement becomes one all-to-all). See §Perf.
        xf = constrain(xf, ("dp", None))
    e = p["router"].shape[1]

    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)                    # (T, E)
    gate_vals, idx = jax.lax.top_k(probs, top_k)               # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9
    )                                                          # renormalize

    cap = int(max(1, round(t * top_k / e * capacity_factor)))
    if dispatch_sharding == "ep" and cap > 256:
        cap = ((cap + 255) // 256) * 256  # divisible for (tp, dp) sharding
    # position of each (t, k) inside its expert: cumulative count over the
    # flattened (k-major) slot order
    flat_e = idx.T.reshape(-1)                                  # (K*T,)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)         # (K*T, E)
    pos = jnp.cumsum(onehot, axis=0) - onehot                   # count before me
    my_pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = my_pos < cap
    slot = jnp.where(keep, flat_e * cap + my_pos, e * cap)      # sentinel drop

    xk = jnp.tile(xf, (top_k, 1))                               # (K*T, d)
    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[slot].add(xk)
    buf = buf[:-1].reshape(e, cap, d)
    if dispatch_sharding == "ep":
        # experts over the TP axis, capacity rows over DP: the scatter
        # becomes one all-to-all instead of gather+all-reduce chains
        buf = constrain(buf, ("tp", "dp", None))
    elif dispatch_sharding == "tokens_dp":
        buf = constrain(buf, ("tp", None, None))  # pure EP on experts

    # expert FFN (SwiGLU) — expert-parallel einsum
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p["w_down"])

    yf = y.reshape(e * cap, d)
    yf = jnp.concatenate([yf, jnp.zeros((1, d), y.dtype)])      # sentinel row
    gathered = yf[slot]                                         # (K*T, d)
    w = (gate_vals.T.reshape(-1) * keep).astype(x.dtype)        # (K*T,)
    out = (gathered * w[:, None]).reshape(top_k, t, d).sum(0)

    if "sh_gate" in p:
        gs = jnp.einsum("td,df->tf", xf, p["sh_gate"])
        us = jnp.einsum("td,df->tf", xf, p["sh_up"])
        out = out + jnp.einsum("tf,fd->td", jax.nn.silu(gs) * us, p["sh_down"])

    # aux losses: Switch load-balance + router z-loss
    frac_tokens = jnp.mean(jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), 0)
    frac_probs = probs.mean(0)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    zloss = jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
    return out.reshape(b, s, d), dict(load_balance=aux, z_loss=zloss)
