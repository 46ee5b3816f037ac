"""Attention layer: plans + prefill/decode apply, with sequence-parallel decode.

Three execution paths:
  * prefill (Tq == Tk): chunked-q attention — ``xla`` (lax.map over q chunks,
    memory-bounded, clean HLO for the dry-run/roofline) or the Pallas flash
    kernel on TPU;
  * decode (Tq == 1 vs cache): plain einsum, or — when the installed sharding
    rules put the cache's sequence axis on a mesh axis ("kv_seq_decode") —
    an explicit shard_map flash-decode combine: per-shard partial
    (max, sumexp, acc) + 2-scalar psum (the DistAttention pattern,
    paper-related work [80]);
  * GQA throughout (n_kv_heads <= n_heads).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.kernels import ops as kops
from repro.models import sharding
from repro.models.common import Leaf, rope

__all__ = ["attn_plan", "attn_prefill", "attn_decode", "chunked_mha"]


def attn_plan(cfg: ArchConfig) -> Dict[str, Leaf]:
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    p = {
        "wq": Leaf((d, hq * dh), ("embed", "heads")),
        "wk": Leaf((d, hkv * dh), ("embed", "kv_heads")),
        "wv": Leaf((d, hkv * dh), ("embed", "kv_heads")),
        "wo": Leaf((hq * dh, d), ("heads", "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = Leaf((hq * dh,), ("heads",), "zeros")
        p["bk"] = Leaf((hkv * dh,), ("kv_heads",), "zeros")
        p["bv"] = Leaf((hkv * dh,), ("kv_heads",), "zeros")
    return p


def _project_qkv(cfg: ArchConfig, p, x, positions):
    """Returns (q_roped, k_roped, v, k_pre_rope)."""
    B, T, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, T, cfg.n_heads, cfg.d_head)
    k = k.reshape(B, T, cfg.n_kv_heads, cfg.d_head)
    v = v.reshape(B, T, cfg.n_kv_heads, cfg.d_head)
    q = rope(q, positions, cfg.rope_theta)
    k_pre = k
    k = rope(k, positions, cfg.rope_theta)
    q = sharding.constrain(q, "batch", "seq", "act_heads", "head_dim")
    k = sharding.constrain(k, "batch", "seq", "act_kv", "head_dim")
    v = sharding.constrain(v, "batch", "seq", "act_kv", "head_dim")
    return q, k, v, k_pre


def chunked_mha(
    q: jnp.ndarray,  # (B, Tq, Hq, D)
    k: jnp.ndarray,  # (B, Tk, Hkv, D)
    v: jnp.ndarray,
    *,
    causal: bool,
    prefix_len: Optional[jnp.ndarray],
    chunk: int,
    shard_repeated_kv: bool = False,
) -> jnp.ndarray:
    """Memory-bounded attention: full Tk per q-chunk, f32 softmax."""
    B, Tq, Hq, D = q.shape
    Hkv = k.shape[2]
    rep = Hq // Hkv
    scale = 1.0 / np.sqrt(D)
    kh = jnp.repeat(k, rep, axis=2)
    vh = jnp.repeat(v, rep, axis=2)
    if shard_repeated_kv:
        # shard the GQA-expanded K/V over the head axis so the repeat never
        # materializes replicated (baseline memory hotspot, §Perf)
        kh = sharding.constrain(kh, "batch", "seq", "act_heads", "head_dim")
        vh = sharding.constrain(vh, "batch", "seq", "act_heads", "head_dim")
    Tk = k.shape[1]
    chunk = min(chunk, Tq)
    n_chunks = -(-Tq // chunk)
    pad = n_chunks * chunk - Tq
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else q
    qc = qp.reshape(B, n_chunks, chunk, Hq, D)

    k_pos = jnp.arange(Tk)

    def one_chunk(ci):
        qi = qc[:, ci]  # (B, chunk, Hq, D)
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, kh).astype(jnp.float32) * scale
        if causal:
            q_pos = ci * chunk + jnp.arange(chunk) + (Tk - Tq)
            mask = k_pos[None, :] <= q_pos[:, None]  # (chunk, Tk)
            if prefix_len is not None:
                mask = mask[None] | (k_pos[None, None, :] < prefix_len[:, None, None])
                mask = mask[:, None]  # (B,1,chunk,Tk)
            else:
                mask = mask[None, None]
            s = jnp.where(mask, s, -1e30)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w.astype(vh.dtype), vh)

    out = jax.lax.map(one_chunk, jnp.arange(n_chunks))  # (nc, B, chunk, Hq, D)
    out = jnp.moveaxis(out, 0, 1).reshape(B, n_chunks * chunk, Hq, D)
    return out[:, :Tq]


def attn_prefill(
    cfg: ArchConfig,
    p: Dict[str, jnp.ndarray],
    x: jnp.ndarray,  # (B, T, d)
    positions: jnp.ndarray,  # (B, T)
    *,
    causal: bool = True,
    prefix_len: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Returns (attn_out (B,T,d), (k, v) each (B,T,Hkv,Dh)) — the KV cache.

    With ``cfg.prerope_kv_cache`` the cached K is pre-RoPE (decode rotates
    it at read time); attention math always uses roped K.
    """
    q, k, v, k_pre = _project_qkv(cfg, p, x, positions)
    if cfg.attention_impl == "xla":
        o = chunked_mha(
            q, k, v, causal=causal, prefix_len=prefix_len, chunk=cfg.attn_chunk,
            shard_repeated_kv=cfg.shard_repeated_kv,
        )
    else:
        o = kops.mha(
            jnp.moveaxis(q, 2, 1),
            jnp.moveaxis(k, 2, 1),
            jnp.moveaxis(v, 2, 1),
            prefix_len,
            causal=causal,
            impl=cfg.attention_impl,
        )
        o = jnp.moveaxis(o, 1, 2)
    B, T, _, _ = q.shape
    out = o.reshape(B, T, cfg.n_heads * cfg.d_head) @ p["wo"]
    k_cache = k_pre if cfg.prerope_kv_cache else k
    return out, (k_cache, v)


def cross_attn_prefill(
    cfg: ArchConfig,
    p: Dict[str, jnp.ndarray],
    x: jnp.ndarray,  # decoder states (B, T, d)
    memory_kv: Tuple[jnp.ndarray, jnp.ndarray],  # (B, S, Hkv, Dh) x2
) -> jnp.ndarray:
    B, T, _ = x.shape
    q = x @ p["wq"]
    if cfg.qkv_bias:
        q = q + p["bq"]
    q = q.reshape(B, T, cfg.n_heads, cfg.d_head)
    k, v = memory_kv
    o = chunked_mha(
        q, k, v, causal=False, prefix_len=None, chunk=cfg.attn_chunk,
        shard_repeated_kv=cfg.shard_repeated_kv,
    )
    return o.reshape(B, T, cfg.n_heads * cfg.d_head) @ p["wo"]


def memory_kv(cfg: ArchConfig, p, mem: jnp.ndarray):
    """Project encoder memory once into cross-attention K/V."""
    B, S, _ = mem.shape
    k = mem @ p["wk"]
    v = mem @ p["wv"]
    if cfg.qkv_bias:
        k, v = k + p["bk"], v + p["bv"]
    return (
        k.reshape(B, S, cfg.n_kv_heads, cfg.d_head),
        v.reshape(B, S, cfg.n_kv_heads, cfg.d_head),
    )


def _decode_mha_plain(q, kc, vc, kv_len):
    # q (B,Hq,D); kc/vc (B,S,Hkv,D)
    B, Hq, D = q.shape
    Hkv = kc.shape[2]
    rep = Hq // Hkv
    scale = 1.0 / np.sqrt(D)
    qg = q.reshape(B, Hkv, rep, D)
    s = jnp.einsum("bkrd,bskd->bkrs", qg, kc).astype(jnp.float32) * scale
    S = kc.shape[1]
    mask = jnp.arange(S)[None, None, None, :] < kv_len[:, None, None, None]
    s = jnp.where(mask, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkrs,bskd->bkrd", w.astype(vc.dtype), vc)
    return o.reshape(B, Hq, D)


def _decode_mha_sp(q, kc, vc, kv_len, mesh, seq_axis: str):
    """Sequence-parallel decode: cache S-axis sharded over ``seq_axis``."""
    batch_axes = sharding.logical_to_spec(("batch",))[0]

    def local(q, kc, vc, kv_len):
        # shapes here are per-shard; S_loc = S / n_shards
        idx = jax.lax.axis_index(seq_axis)
        B, Hq, D = q.shape
        S_loc = kc.shape[1]
        Hkv = kc.shape[2]
        rep = Hq // Hkv
        scale = 1.0 / np.sqrt(D)
        qg = q.reshape(B, Hkv, rep, D)
        s = jnp.einsum("bkrd,bskd->bkrs", qg, kc).astype(jnp.float32) * scale
        pos = idx * S_loc + jnp.arange(S_loc)
        mask = pos[None, None, None, :] < kv_len[:, None, None, None]
        s = jnp.where(mask, s, -1e30)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        acc = jnp.einsum("bkrs,bskd->bkrd", p.astype(vc.dtype), vc).astype(
            jnp.float32
        )
        m_glob = jax.lax.pmax(m, seq_axis)
        corr = jnp.exp(m - m_glob)
        l_glob = jax.lax.psum(l * corr, seq_axis)
        acc_glob = jax.lax.psum(acc * corr[..., 0][..., None], seq_axis)
        o = acc_glob / jnp.maximum(l_glob[..., 0][..., None], 1e-30)
        return o.reshape(B, Hq, D).astype(q.dtype)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(batch_axes, None, None),
            P(batch_axes, seq_axis, None, None),
            P(batch_axes, seq_axis, None, None),
            P(batch_axes),
        ),
        out_specs=P(batch_axes, None, None),
        check_vma=False,
    )(q, kc, vc, kv_len)


def attn_decode(
    cfg: ArchConfig,
    p: Dict[str, jnp.ndarray],
    x: jnp.ndarray,  # (B, 1, d)
    cache: Tuple[jnp.ndarray, jnp.ndarray],  # (B, S, Hkv, Dh) x2
    cache_len: jnp.ndarray,  # (B,) tokens already in cache
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """One-token decode; returns (out (B,1,d), updated cache)."""
    B = x.shape[0]
    positions = cache_len[:, None]  # (B,1)
    q, k, v, k_pre = _project_qkv(cfg, p, x, positions)
    kc, vc = cache
    # write new token at cache_len (uniform position assumed for the batch;
    # ragged per-request positions are handled by the serving engine batching
    # same-length groups)
    upd = jax.vmap(
        lambda c, new, i: jax.lax.dynamic_update_slice_in_dim(c, new, i, axis=0)
    )
    k_wr = k_pre if cfg.prerope_kv_cache else k
    kc = upd(kc, k_wr[:, 0:1].astype(kc.dtype), cache_len)
    vc = upd(vc, v[:, 0:1].astype(vc.dtype), cache_len)
    kv_len = cache_len + 1
    if cfg.prerope_kv_cache:
        # rotate the whole cache at read time (position grid 0..S)
        S = kc.shape[1]
        pos_grid = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        kc_read = rope(kc, pos_grid, cfg.rope_theta)
    else:
        kc_read = kc

    mesh = sharding.current_mesh()
    seq_axis = sharding.logical_to_spec(("kv_seq_decode",))[0] if mesh else None
    if (
        cfg.attention_impl in ("pallas", "pallas_interpret")
        and mesh is None
    ):
        o = kops.decode_attention(
            jnp.moveaxis(q[:, 0:1], 2, 1)[:, :, 0],
            jnp.moveaxis(kc_read, 2, 1),
            jnp.moveaxis(vc, 2, 1),
            kv_len,
            impl=cfg.attention_impl,
        )
    elif mesh is not None and seq_axis is not None:
        o = _decode_mha_sp(q[:, 0], kc_read, vc, kv_len, mesh, seq_axis)
    else:
        o = _decode_mha_plain(q[:, 0], kc_read, vc, kv_len)
    out = o.reshape(B, 1, cfg.n_heads * cfg.d_head) @ p["wo"]
    return out, (kc, vc)
