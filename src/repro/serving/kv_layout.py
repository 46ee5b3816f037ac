"""Bridges between the serving engine's KV cache pytrees and the codec's
(L, 2, T, C) tensor layout, plus cache allocation helpers and the row-pool
primitives (save / restore / reset of a single request's row) that let the
continuous-admission scheduler recycle rows of one batch-of-requests cache
across sessions and suspend a preempted session's realized KV for later
resumption.

Shard-aware row addressing (mesh-sharded serving).  Schedulers and every
public entry point name rows by *global* index ``r`` in ``[0, B)``.  When
the cache's row axis is split over a mesh axis of ``S`` shards (blocked
layout, matching ``NamedSharding`` partitioning), global row ``r`` lives on
shard ``r // (B / S)`` at local row ``r % (B / S)``.  The ``*_local``
kernels below are the per-shard shard_map bodies of the global primitives:
each receives its shard's ``(L, B/S, cap, Hkv, Dh)`` cache slice plus the
*replicated* global row operands, recovers local indices from
``jax.lax.axis_index``, and masks out rows that belong to other shards —
so every shard performs exactly the row-local arithmetic of the unsharded
kernel, byte for byte, and runs addressed to foreign shards are dropped via
a discarded scratch row rather than branching."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models.lm import Caches, masked_window_update

__all__ = [
    "RowSnapshot",
    "caches_to_codec_kv",
    "codec_kv_to_caches",
    "insert_codec_run",
    "insert_codec_runs",
    "insert_codec_runs_local",
    "extract_row",
    "save_row",
    "restore_row",
    "restore_row_local",
    "reset_rows",
    "reset_rows_local",
    "alloc_caches",
    "kv_cache_bytes",
]


def insert_codec_run(
    kv_k: jnp.ndarray,  # (L, B, cap, Hkv, Dh) serving cache, donatable
    kv_v: jnp.ndarray,
    length: jnp.ndarray,  # (B,) int32
    kv_new: jnp.ndarray,  # (L, 2, T, C) decoded run (codec.decode_chunks)
    start: jnp.ndarray,  # scalar int32 token offset
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Write a decoded codec run into the serving cache at ``[start, start+T)``.

    Pure function meant to be jitted with the cache buffers donated
    (``Engine.decode_to_cache``): the reshape to the attention layout
    ``(L, B, T, Hkv, Dh)`` is a view, the batch broadcast fuses into the
    ``dynamic_update_slice`` write, and with donation XLA updates the cache
    in place instead of copying O(cache_size) per insertion.  ``length``
    advances monotonically (``maximum``) so interleaved TEXT/bitstream chunk
    orders can never shrink the cache.
    """
    L, B, _, Hkv, Dh = kv_k.shape
    T = kv_new.shape[2]
    kt = jnp.broadcast_to(
        kv_new[:, 0].reshape(L, 1, T, Hkv, Dh).astype(kv_k.dtype), (L, B, T, Hkv, Dh)
    )
    vt = jnp.broadcast_to(
        kv_new[:, 1].reshape(L, 1, T, Hkv, Dh).astype(kv_v.dtype), (L, B, T, Hkv, Dh)
    )
    start = start.astype(jnp.int32)
    zero = jnp.int32(0)
    kv_k = jax.lax.dynamic_update_slice(kv_k, kt, (zero, zero, start, zero, zero))
    kv_v = jax.lax.dynamic_update_slice(kv_v, vt, (zero, zero, start, zero, zero))
    length = jnp.maximum(length, start + T)
    return kv_k, kv_v, length


def insert_codec_runs(
    kv_k: jnp.ndarray,  # (L, B, cap, Hkv, Dh) batch-of-requests cache, donatable
    kv_v: jnp.ndarray,
    length: jnp.ndarray,  # (B,) int32
    kv_new: jnp.ndarray,  # (L, 2, sum_T, C) decoded concat of all runs
    rows: jnp.ndarray,  # (R,) int32 cache row per run (distinct)
    starts: jnp.ndarray,  # (R,) int32 token offset per run
    run_tokens: Tuple[int, ...],  # static: token count per run, concat order
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Write R decoded runs — one per *request* — into their cache rows.

    The multi-session counterpart of :func:`insert_codec_run`: the cache's
    batch axis holds different requests (one row per live session), and each
    run lands at its own row and token offset in a single dispatch — a
    vmap'd per-row-offset ``dynamic_update_slice`` instead of one dispatch
    per request per run.  Meant to be jitted with the cache buffers donated
    (``Engine.insert_runs``).

    Only run geometry (``run_tokens``, and the batch/capacity shapes) is
    static; ``rows`` and ``starts`` are data, so which session received
    which run never retraces the program.  Rows not named in ``rows`` are
    written back byte-identically (their window merge keeps every current
    value).  Requires ``cap >= max(run_tokens)``; rows whose window would
    overhang the capacity are handled exactly via a shifted in-window merge
    (``dynamic_update_slice`` clamps the window start; the merge re-aligns
    the new tokens inside it).
    """
    L, B, cap, Hkv, Dh = kv_k.shape
    R = len(run_tokens)
    t_max = max(run_tokens)
    # per-run padded updates in the attention layout, stacked: (R, L, Tm, ...)
    off = 0
    ks, vs = [], []
    for T in run_tokens:
        piece = kv_new[:, :, off : off + T].reshape(L, 2, T, Hkv, Dh)
        pad = ((0, 0), (0, 0), (0, t_max - T), (0, 0), (0, 0))
        piece = jnp.pad(piece, pad)
        ks.append(piece[:, 0])
        vs.append(piece[:, 1])
        off += T
    k_upd = jnp.stack(ks).astype(kv_k.dtype)  # (R, L, Tm, Hkv, Dh)
    v_upd = jnp.stack(vs).astype(kv_v.dtype)
    rows = rows.astype(jnp.int32)
    starts = starts.astype(jnp.int32)
    widths = jnp.asarray(run_tokens, jnp.int32)

    # scatter run payloads/offsets to their cache rows (inactive rows: width 0)
    row_k = jnp.zeros((B, L, t_max, Hkv, Dh), kv_k.dtype).at[rows].set(k_upd)
    row_v = jnp.zeros((B, L, t_max, Hkv, Dh), kv_v.dtype).at[rows].set(v_upd)
    row_start = jnp.zeros((B,), jnp.int32).at[rows].set(starts)
    row_width = jnp.zeros((B,), jnp.int32).at[rows].set(widths)

    # one shifted read-merge-write window per (row, layer): rows not named
    # in `rows` have width 0 and are written back verbatim; a run whose
    # padded window overhangs the capacity is re-aligned inside it (see
    # lm.masked_window_update, the single shared implementation)
    _one_row = jax.vmap(  # over layers: cache_row (L, cap, ...), upd (L, Tm, ...)
        masked_window_update, in_axes=(0, 0, None, None)
    )
    vrow = jax.vmap(_one_row, in_axes=(1, 0, 0, 0), out_axes=1)
    kv_k = vrow(kv_k, row_k, row_start, row_width)
    kv_v = vrow(kv_v, row_v, row_start, row_width)
    length = jnp.maximum(length, row_start + row_width)
    return kv_k, kv_v, length


def _local_rows(rows: jnp.ndarray, b_loc: int, axis: Optional[str]):
    """Map replicated global row ids to this shard's local indices.

    Returns ``(local, mine)``: foreign rows get the out-of-range local
    index ``b_loc`` (a scratch/drop slot — never a wrapped negative index,
    which jnp scatter would interpret Python-style)."""
    shard = jax.lax.axis_index(axis) if axis is not None else 0
    local = rows.astype(jnp.int32) - shard * b_loc
    mine = (local >= 0) & (local < b_loc)
    return jnp.where(mine, local, b_loc), mine


def insert_codec_runs_local(
    kv_k: jnp.ndarray,  # (L, B/S, cap, Hkv, Dh) this shard's cache slice
    kv_v: jnp.ndarray,
    length: jnp.ndarray,  # (B/S,) int32 this shard's lengths
    kv_new: jnp.ndarray,  # (L, 2, sum_T, C) decoded concat, replicated
    rows: jnp.ndarray,  # (R,) int32 *global* cache row per run, replicated
    starts: jnp.ndarray,  # (R,) int32 token offset per run, replicated
    run_tokens: Tuple[int, ...],  # static: token count per run
    axis: Optional[str],  # mesh axis the row dim is split over
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-shard shard_map body of :func:`insert_codec_runs`.

    Identical merge arithmetic to the global kernel, restricted to this
    shard's rows: runs addressed to other shards are scattered into an
    extra scratch row at index ``B/S`` (sliced off before the window
    merge), so local rows they would otherwise alias keep width 0 and are
    written back byte-identically.  Every run's payload is replicated to
    all shards (runs are small — a few chunks — next to the cache), which
    keeps the body collective-free.
    """
    L, b_loc, cap, Hkv, Dh = kv_k.shape
    t_max = max(run_tokens)
    off = 0
    ks, vs = [], []
    for T in run_tokens:
        piece = kv_new[:, :, off : off + T].reshape(L, 2, T, Hkv, Dh)
        pad = ((0, 0), (0, 0), (0, t_max - T), (0, 0), (0, 0))
        piece = jnp.pad(piece, pad)
        ks.append(piece[:, 0])
        vs.append(piece[:, 1])
        off += T
    k_upd = jnp.stack(ks).astype(kv_k.dtype)  # (R, L, Tm, Hkv, Dh)
    v_upd = jnp.stack(vs).astype(kv_v.dtype)
    local, _ = _local_rows(rows, b_loc, axis)
    starts = starts.astype(jnp.int32)
    widths = jnp.asarray(run_tokens, jnp.int32)

    # scatter into B/S + 1 rows: foreign runs pile into the scratch row
    # (duplicate-index scatter there is unspecified but discarded)
    row_k = (
        jnp.zeros((b_loc + 1, L, t_max, Hkv, Dh), kv_k.dtype)
        .at[local].set(k_upd)[:b_loc]
    )
    row_v = (
        jnp.zeros((b_loc + 1, L, t_max, Hkv, Dh), kv_v.dtype)
        .at[local].set(v_upd)[:b_loc]
    )
    row_start = jnp.zeros((b_loc + 1,), jnp.int32).at[local].set(starts)[:b_loc]
    row_width = jnp.zeros((b_loc + 1,), jnp.int32).at[local].set(widths)[:b_loc]

    _one_row = jax.vmap(masked_window_update, in_axes=(0, 0, None, None))
    vrow = jax.vmap(_one_row, in_axes=(1, 0, 0, 0), out_axes=1)
    kv_k = vrow(kv_k, row_k, row_start, row_width)
    kv_v = vrow(kv_v, row_v, row_start, row_width)
    length = jnp.maximum(length, row_start + row_width)
    return kv_k, kv_v, length


def restore_row_local(
    kv_k: jnp.ndarray,  # (L, B/S, cap, Hkv, Dh) this shard's cache slice
    kv_v: jnp.ndarray,
    length: jnp.ndarray,  # (B/S,) int32
    k_row: jnp.ndarray,  # (L, T, Hkv, Dh) saved tokens, replicated
    v_row: jnp.ndarray,
    row: jnp.ndarray,  # scalar int32 *global* target row, replicated
    axis: Optional[str],
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-shard shard_map body of :func:`restore_row`: the shard owning
    the global row writes the snapshot at its local index; every other
    shard round-trips the addressed slot's current bytes (a masked
    read-merge-write, so no branch and no cross-shard traffic)."""
    L, b_loc, cap, Hkv, Dh = kv_k.shape
    T = k_row.shape[1]
    local, mine = _local_rows(row.reshape(1), b_loc, axis)
    li = jnp.minimum(local[0], b_loc - 1)  # clamp the foreign scratch index
    own = mine[0]
    zero = jnp.int32(0)
    cur_k = jax.lax.dynamic_slice(
        kv_k, (zero, li, zero, zero, zero), (L, 1, T, Hkv, Dh)
    )
    cur_v = jax.lax.dynamic_slice(
        kv_v, (zero, li, zero, zero, zero), (L, 1, T, Hkv, Dh)
    )
    new_k = jnp.where(own, k_row[:, None].astype(kv_k.dtype), cur_k)
    new_v = jnp.where(own, v_row[:, None].astype(kv_v.dtype), cur_v)
    kv_k = jax.lax.dynamic_update_slice(kv_k, new_k, (zero, li, zero, zero, zero))
    kv_v = jax.lax.dynamic_update_slice(kv_v, new_v, (zero, li, zero, zero, zero))
    length = length.at[li].set(jnp.where(own, jnp.int32(T), length[li]))
    return kv_k, kv_v, length


def reset_rows_local(
    kv_k: jnp.ndarray,  # (L, B/S, cap, Hkv, Dh) this shard's cache slice
    kv_v: jnp.ndarray,
    length: jnp.ndarray,  # (B/S,) int32
    rows: jnp.ndarray,  # (R,) int32 *global* rows to recycle, replicated
    axis: Optional[str],
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-shard shard_map body of :func:`reset_rows`: each shard zeroes
    the recycled rows it owns; foreign rows map to the out-of-range scratch
    index and their scatter updates are dropped."""
    b_loc = kv_k.shape[1]
    local, _ = _local_rows(rows, b_loc, axis)
    kv_k = kv_k.at[:, local].set(jnp.zeros((), kv_k.dtype), mode="drop")
    kv_v = kv_v.at[:, local].set(jnp.zeros((), kv_v.dtype), mode="drop")
    length = length.at[local].set(0, mode="drop")
    return kv_k, kv_v, length


@dataclasses.dataclass
class RowSnapshot:
    """A suspended session's realized KV: the first ``n_tokens`` tokens of
    its cache row, sliced out as standalone device arrays (independent of
    the pool cache's buffers, so later donated inserts into the pool cannot
    invalidate it).  Restored — possibly into a *different* row — by
    :func:`restore_row`."""

    kv_k: jnp.ndarray  # (L, T, Hkv, Dh)
    kv_v: jnp.ndarray  # (L, T, Hkv, Dh)
    n_tokens: int


def save_row(caches: Caches, row: int, n_tokens: int) -> RowSnapshot:
    """Snapshot the realized prefix of one request's cache row.

    The slices force their own buffers, so the snapshot survives any number
    of donated-buffer updates to the pool cache afterwards; the exact bytes
    come back via :func:`restore_row` (suspend→resume is a bit-exact round
    trip — held to that by tests/test_continuous.py).
    """
    n = int(n_tokens)
    return RowSnapshot(
        kv_k=caches.kv_k[:, row, :n],
        kv_v=caches.kv_v[:, row, :n],
        n_tokens=n,
    )


def restore_row(
    kv_k: jnp.ndarray,  # (L, B, cap, Hkv, Dh) pool cache, donatable
    kv_v: jnp.ndarray,
    length: jnp.ndarray,  # (B,) int32
    k_row: jnp.ndarray,  # (L, T, Hkv, Dh) saved tokens (RowSnapshot.kv_k)
    v_row: jnp.ndarray,
    row: jnp.ndarray,  # scalar int32 target row (data, not static)
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Re-insert a suspended session's saved tokens at ``[0, T)`` of ``row``.

    Meant to be jitted with the cache buffers donated (``Engine.
    restore_row``); the target row is data, so resuming into whichever row
    freed does not retrace.  The row must have been reset (length 0) before
    restoring — the pool hands out recycled rows zeroed.
    """
    T = k_row.shape[1]
    row = row.astype(jnp.int32)
    zero = jnp.int32(0)
    kv_k = jax.lax.dynamic_update_slice(
        kv_k, k_row[:, None].astype(kv_k.dtype), (zero, row, zero, zero, zero)
    )
    kv_v = jax.lax.dynamic_update_slice(
        kv_v, v_row[:, None].astype(kv_v.dtype), (zero, row, zero, zero, zero)
    )
    length = length.at[row].set(jnp.int32(T))
    return kv_k, kv_v, length


def reset_rows(
    kv_k: jnp.ndarray,  # (L, B, cap, Hkv, Dh) pool cache, donatable
    kv_v: jnp.ndarray,
    length: jnp.ndarray,  # (B,) int32
    rows: jnp.ndarray,  # (R,) int32 rows to recycle
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Zero recycled rows before a new session takes them.

    A recycled row must look exactly like a row of a fresh
    :func:`alloc_caches` cache: zero KV and zero length — the length reset
    matters doubly because run insertion advances length *monotonically*
    (``jnp.maximum``), so a stale tenant's length would corrupt the new
    tenant's offsets.  Row membership is data (no retrace per row set).
    """
    rows = rows.astype(jnp.int32)
    kv_k = kv_k.at[:, rows].set(jnp.zeros((), kv_k.dtype))
    kv_v = kv_v.at[:, rows].set(jnp.zeros((), kv_v.dtype))
    length = length.at[rows].set(0)
    return kv_k, kv_v, length


def extract_row(caches: Caches, row: int) -> Caches:
    """One request's batch-1 copy of a batch-of-requests cache.

    The copy is forced: slicing the only row of a one-row cache returns the
    pool's own buffer, which the next donated update of the pool deletes.
    """
    sl = slice(row, row + 1)

    def own(x, batch_axis=1):
        if x is None:
            return None
        return jnp.copy(x[sl] if batch_axis == 0 else x[:, sl])

    return caches._replace(
        kv_k=own(caches.kv_k),
        kv_v=own(caches.kv_v),
        length=own(caches.length, batch_axis=0),
        mamba_conv=own(caches.mamba_conv),
        mamba_ssm=own(caches.mamba_ssm),
        shared_k=own(caches.shared_k),
        shared_v=own(caches.shared_v),
    )


def caches_to_codec_kv(caches: Caches, batch_index: int, n_tokens: int) -> np.ndarray:
    """Extract one request's KV as (L, 2, T, C) float32 for encoding."""
    k = np.asarray(caches.kv_k[:, batch_index, :n_tokens], dtype=np.float32)
    v = np.asarray(caches.kv_v[:, batch_index, :n_tokens], dtype=np.float32)
    L, T, Hkv, Dh = k.shape
    k = k.reshape(L, T, Hkv * Dh)
    v = v.reshape(L, T, Hkv * Dh)
    return np.stack([k, v], axis=1)  # (L, 2, T, C)


def codec_kv_to_caches(
    kv: np.ndarray,  # (L, 2, T, C)
    cfg: ArchConfig,
    *,
    batch: int = 1,
    capacity: Optional[int] = None,
    dtype=jnp.bfloat16,
) -> Caches:
    """Materialize decoded KV into a serving cache (single request, replicated
    across ``batch`` rows for batched generation experiments)."""
    L, two, T, C = kv.shape
    Hkv, Dh = cfg.n_kv_heads, cfg.d_head
    assert C == Hkv * Dh, f"C={C} != {Hkv}x{Dh}"
    cap = capacity or T
    k = jnp.zeros((L, batch, cap, Hkv, Dh), dtype)
    v = jnp.zeros((L, batch, cap, Hkv, Dh), dtype)
    kt = jnp.asarray(kv[:, 0].reshape(L, T, Hkv, Dh), dtype)
    vt = jnp.asarray(kv[:, 1].reshape(L, T, Hkv, Dh), dtype)
    k = k.at[:, :, :T].set(kt[:, None])
    v = v.at[:, :, :T].set(vt[:, None])
    return Caches(
        kv_k=k,
        kv_v=v,
        length=jnp.full((batch,), T, jnp.int32),
        mamba_conv=None,
        mamba_ssm=None,
        shared_k=None,
        shared_v=None,
    )


def alloc_caches(cfg: ArchConfig, batch: int, capacity: int, dtype=jnp.bfloat16) -> Caches:
    """Empty caches for attention families."""
    L, Hkv, Dh = cfg.n_layers, cfg.n_kv_heads, cfg.d_head
    return Caches(
        kv_k=jnp.zeros((L, batch, capacity, Hkv, Dh), dtype),
        kv_v=jnp.zeros((L, batch, capacity, Hkv, Dh), dtype),
        length=jnp.zeros((batch,), jnp.int32),
        mamba_conv=None,
        mamba_ssm=None,
        shared_k=None,
        shared_v=None,
    )


def kv_cache_bytes(cfg: ArchConfig, n_tokens: int, dtype_bytes: int = 2) -> int:
    """Raw KV cache size for one request (the paper's '25 GB for 16K' figure)."""
    if cfg.family == "hybrid":
        n_apps = cfg.n_layers // max(cfg.shared_block_every, 1)
        return n_apps * 2 * n_tokens * cfg.kv_channels * dtype_bytes
    if not cfg.has_kv_cache:
        return 0
    L = cfg.dec_layers if cfg.family == "encdec" else cfg.n_layers
    return L * 2 * n_tokens * cfg.kv_channels * dtype_bytes
