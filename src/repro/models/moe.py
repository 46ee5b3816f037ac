"""Mixture-of-Experts FFN with sort-based capacity dispatch (EP-shardable).

Top-k routing -> stable-sort slots by expert -> position-in-expert via
searchsorted -> scatter into a dense (E, capacity, d) buffer (overflow
dropped, GShard-style) -> block-diagonal expert matmuls (MXU friendly,
experts sharded over the "experts" logical axis = EP) -> weighted combine.

Static shapes throughout (capacity factor), so the same code lowers for the
dry run and runs the smoke tests.  Shared experts (qwen2-moe) are a plain
dense MLP over all tokens added to the routed output.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import sharding
from repro.models.common import Leaf

__all__ = ["moe_plan", "moe_apply"]


def moe_plan(cfg: ArchConfig) -> Dict[str, Leaf]:
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    d_axis = None if cfg.moe_replicate_d else "embed"
    p = {
        "router": Leaf((d, E), ("embed", None), scale=0.02),
        "w_gate": Leaf((E, d, ff), ("experts", d_axis, "mlp")),
        "w_up": Leaf((E, d, ff), ("experts", d_axis, "mlp")),
        "w_down": Leaf((E, ff, d), ("experts", "mlp", d_axis)),
    }
    if cfg.n_shared_experts:
        sff = ff * cfg.n_shared_experts
        p["shared"] = {
            "w_gate": Leaf((d, sff), ("embed", "mlp")),
            "w_up": Leaf((d, sff), ("embed", "mlp")),
            "w_down": Leaf((sff, d), ("mlp", "embed")),
        }
    return p


def moe_apply(
    cfg: ArchConfig, p: Dict[str, jnp.ndarray], x: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, T, d) -> (out, aux_loss).  Dispatch per cfg.moe_dispatch."""
    if cfg.moe_dispatch == "grouped":
        return _moe_apply_grouped(cfg, p, x)
    return _moe_apply_global(cfg, p, x)


def _moe_apply_global(
    cfg: ArchConfig, p: Dict[str, jnp.ndarray], x: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    B, T, d = x.shape
    E, k = cfg.n_experts, cfg.moe_topk
    N = B * T
    xf = x.reshape(N, d)

    logits = (xf @ p["router"]).astype(jnp.float32)  # (N, E)
    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(gates, k)  # (N, k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balancing aux loss.
    density = jnp.mean(
        jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32), axis=0
    )
    aux = E * jnp.sum(density * jnp.mean(gates, axis=0))

    # round capacity to a multiple of 128 so the (E, capacity, d) dispatch
    # buffer's capacity axis shards evenly over the dp axes
    capacity = int(max(1, round(N * k / E * cfg.capacity_factor)))
    capacity = -(-capacity // 128) * 128

    flat_e = topi.reshape(-1).astype(jnp.int32)  # (N*k,)
    flat_w = topv.reshape(-1)
    flat_t = (
        jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32)[:, None], (N, k)).reshape(-1)
    )
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    sorted_t = flat_t[order]
    sorted_w = flat_w[order]
    seg_start = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos = jnp.arange(N * k, dtype=jnp.int32) - seg_start.astype(jnp.int32)
    keep = pos < capacity
    slot = sorted_e * capacity + jnp.minimum(pos, capacity - 1)
    slot = jnp.where(keep, slot, E * capacity)  # OOB -> dropped

    xs = xf[sorted_t]  # (N*k, d) gather in expert order
    buf = jnp.zeros((E * capacity, d), xf.dtype)
    buf = buf.at[slot].set(xs, mode="drop")
    buf = buf.reshape(E, capacity, d)
    buf = sharding.constrain(buf, None, "expert_cap", "act_embed")

    # Block-diagonal expert SwiGLU.
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    h = jax.nn.silu(g) * u
    h = sharding.constrain(h, None, "expert_cap", "act_mlp")
    y = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    y = sharding.constrain(y, None, "expert_cap", "act_embed")
    y = y.reshape(E * capacity, d)

    y_slot = jnp.where(
        keep[:, None], y.at[slot].get(mode="fill", fill_value=0), 0
    )
    out = jnp.zeros((N, d), y.dtype)
    out = out.at[sorted_t].add(y_slot * sorted_w[:, None].astype(y.dtype))

    if cfg.n_shared_experts:
        sp = p["shared"]
        gate = jax.nn.silu(xf @ sp["w_gate"]) * (xf @ sp["w_up"])
        out = out + gate @ sp["w_down"]

    return out.reshape(B, T, d), aux


def _moe_apply_grouped(
    cfg: ArchConfig, p: Dict[str, jnp.ndarray], x: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """GShard-style grouped dispatch: tokens split into ``moe_groups``
    groups (>= dp shards), each sorted/scattered *locally* with per-group
    capacity.  All dispatch intermediates carry a leading group axis sharded
    over dp, so nothing is replicated across data shards — the fix for the
    global-sort memory blowup visible in the baseline roofline (§Perf)."""
    B, T, d = x.shape
    E, k = cfg.n_experts, cfg.moe_topk
    N = B * T
    G = min(cfg.moe_groups, N)
    while N % G:
        G //= 2
    n_loc = N // G
    xg = x.reshape(G, n_loc, d)
    xg = sharding.constrain(xg, "expert_cap", None, "act_embed")

    logits = (xg @ p["router"]).astype(jnp.float32)  # (G, n, E)
    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(gates, k)  # (G, n, k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    density = jnp.mean(
        jax.nn.one_hot(topi[..., 0], E, dtype=jnp.float32), axis=(0, 1)
    )
    aux = E * jnp.sum(density * jnp.mean(gates, axis=(0, 1)))

    capacity = int(max(8, -(-int(n_loc * k / E * cfg.capacity_factor) // 8) * 8))

    flat_e = topi.reshape(G, n_loc * k).astype(jnp.int32)
    flat_w = topv.reshape(G, n_loc * k)
    flat_t = jnp.broadcast_to(
        jnp.arange(n_loc, dtype=jnp.int32)[:, None], (n_loc, k)
    ).reshape(1, n_loc * k)
    flat_t = jnp.broadcast_to(flat_t, (G, n_loc * k))

    order = jnp.argsort(flat_e, axis=-1, stable=True)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    sorted_t = jnp.take_along_axis(flat_t, order, axis=-1)
    sorted_w = jnp.take_along_axis(flat_w, order, axis=-1)
    seg_start = jax.vmap(lambda a: jnp.searchsorted(a, a, side="left"))(sorted_e)
    pos = jnp.arange(n_loc * k, dtype=jnp.int32)[None] - seg_start.astype(jnp.int32)
    keep = pos < capacity
    slot = sorted_e * capacity + jnp.minimum(pos, capacity - 1)
    slot = jnp.where(keep, slot, E * capacity)

    xs = jnp.take_along_axis(
        xg, sorted_t[..., None].astype(jnp.int32), axis=1
    )  # (G, n*k, d)
    buf = jnp.zeros((G, E * capacity, d), xg.dtype)
    buf = jax.vmap(lambda b, s, v: b.at[s].set(v, mode="drop"))(buf, slot, xs)
    buf = buf.reshape(G, E, capacity, d)
    buf = sharding.constrain(buf, "expert_cap", None, None, "act_embed")

    mesh = sharding.current_mesh()
    mlp_axis = sharding.logical_to_spec(("act_mlp",))[0] if mesh else None
    if mesh is not None and mlp_axis is not None:
        # TP-local expert FFN + combine: keep the ff-partial sums local
        # through the (linear) combine and psum only the final token
        # outputs — turns the 8 GB (E, G, cap, d) all-reduces into
        # (G, n_loc, d) ones (§Perf granite iteration 4).
        out = _grouped_ffn_combine_sm(
            p, buf, slot, sorted_t, sorted_w, keep, mesh, mlp_axis, n_loc
        )
    else:
        g = jnp.einsum("gecd,edf->gecf", buf, p["w_gate"])
        u = jnp.einsum("gecd,edf->gecf", buf, p["w_up"])
        h = jax.nn.silu(g) * u
        h = sharding.constrain(h, "expert_cap", None, None, "act_mlp")
        y = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
        y = sharding.constrain(y, "expert_cap", None, None, "act_embed")
        y = y.reshape(G, E * capacity, d)
        y_slot = jax.vmap(lambda a, s: a.at[s].get(mode="fill", fill_value=0))(y, slot)
        y_slot = jnp.where(keep[..., None], y_slot, 0)
        out = jnp.zeros((G, n_loc, d), y.dtype)
        out = jax.vmap(lambda o, t, v: o.at[t].add(v))(
            out, sorted_t, y_slot * sorted_w[..., None].astype(y.dtype)
        )

    out = out.reshape(B, T, d)
    if cfg.n_shared_experts:
        sp = p["shared"]
        xf = x.reshape(N, d)
        gate = jax.nn.silu(xf @ sp["w_gate"]) * (xf @ sp["w_up"])
        out = out + (gate @ sp["w_down"]).reshape(B, T, d)
    return out, aux


def _grouped_ffn_combine_sm(
    p, buf, slot, sorted_t, sorted_w, keep, mesh, mlp_axis, n_loc
):
    """shard_map expert FFN: ff sharded over ``mlp_axis``, groups over dp;
    partial down-proj outputs are combined locally, then psum'd once."""
    from jax.sharding import PartitionSpec as P

    G, E, capacity, d = buf.shape
    dp = sharding.logical_to_spec(("expert_cap",))[0]

    def local(buf_l, wg_l, wu_l, wd_l, slot_l, st_l, sw_l, keep_l):
        g = jnp.einsum("gecd,edf->gecf", buf_l, wg_l)
        u = jnp.einsum("gecd,edf->gecf", buf_l, wu_l)
        h = jax.nn.silu(g) * u
        y = jnp.einsum("gecf,efd->gecd", h, wd_l)  # partial over mlp shards
        y = y.reshape(buf_l.shape[0], E * capacity, d)
        y_slot = jax.vmap(lambda a, s: a.at[s].get(mode="fill", fill_value=0))(
            y, slot_l
        )
        y_slot = jnp.where(keep_l[..., None], y_slot, 0)
        out = jnp.zeros((buf_l.shape[0], n_loc, d), y.dtype)
        out = jax.vmap(lambda o, t, v: o.at[t].add(v))(
            out, st_l, y_slot * sw_l[..., None].astype(y.dtype)
        )
        return jax.lax.psum(out, mlp_axis)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(dp, None, None, None),
            P(None, None, mlp_axis),
            P(None, None, mlp_axis),
            P(None, mlp_axis, None),
            P(dp, None),
            P(dp, None),
            P(dp, None),
            P(dp, None),
        ),
        out_specs=P(dp, None, None),
        check_vma=False,
    )(buf, p["w_gate"], p["w_up"], p["w_down"], slot, sorted_t, sorted_w, keep)
