"""Serving engine: the paper's two LLM interfaces plus chunked prefill.

Implements (paper §6):
  * ``calculate_kv(context) -> KVCache``  — prefill without generation;
  * ``generate_with_kv(KVCache) -> text`` — generation that skips context
    prefill entirely;
plus ``prefill_extend`` — compute a text chunk's KV on top of already-loaded
chunk KV (the streamer's recompute fallback, paper §5.3 fn. 6) — and a
greedy generation loop used by the examples and quality benchmarks.

One Engine serves many concurrent context loads *and* generations: a single
instance (params, jit caches, one device) is shared by every
``serving.session.ServeSession`` and by the schedulers in
``serving.scheduler``, which allocate a *batch-of-requests* cache (one row
per live session) and drive the batched entry points — ``insert_runs``
(several requests' decoded runs landed at per-row offsets in one dispatch),
``prefill_extend_rows`` (different requests' TEXT recomputes coalesced into
one padded, width-masked forward), and ``decode_step_rows`` (all currently
*generating* sessions' next-token decode stacked into one forward over the
shared cache, per-row length offsets, inactive rows bit-preserved).  The
per-request entry points (``decode_to_cache``, ``prefill_extend``,
``generate_with_kv``) remain the single-session path and the schedulers'
N=1 differential oracles.

All steps are jit-compiled once per (batch, capacity[, run-geometry])
signature and cached.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ArchConfig
from repro.models import lm
from repro.models.lm import Caches
from repro.serving import kv_layout

__all__ = ["Engine"]


class Engine:
    # Shard-aware row addressing: the base engine is a single shard, so the
    # global row space and the local one coincide.  The mesh-sharded
    # subclass (serving.mesh_engine.ShardedEngine) overrides these — the
    # schedulers consult them to size caches (``cache_rows``) and to place
    # rows into per-shard contention/transport domains.
    n_shards: int = 1

    def cache_rows(self, n: int) -> int:
        """Smallest cache batch >= ``n`` this engine can allocate (rounded
        up to a whole number of row shards)."""
        return -(-int(n) // self.n_shards) * self.n_shards

    def __init__(self, cfg: ArchConfig, params, cache_capacity: int = 4096):
        self.cfg = cfg
        self.params = params
        self.capacity = cache_capacity
        self._prefill = jax.jit(
            functools.partial(lm.prefill, cfg), static_argnames=("pad_to",)
        )
        self._decode = jax.jit(functools.partial(lm.decode_step, cfg))
        if cfg.family in ("dense", "moe", "vlm"):
            self._extend = jax.jit(functools.partial(lm.prefill_extend, cfg))
            self._extend_rows = jax.jit(
                lambda params, tokens, caches, widths: lm.prefill_extend(
                    self.cfg, params, tokens, caches, widths=widths
                )
            )

            # Stacked generation step over the batch-of-requests cache: one
            # full-batch decode_step (every row reads/writes at its *own*
            # length offset), then inactive rows' KV/length are merged back
            # so only the generating rows advance.  The merge also
            # neutralizes decode_step's at-capacity clamp for full inactive
            # rows.  Not donated — callers (microbench, oracles) reuse the
            # input caches across steps, matching ``self._decode``.
            def _decode_rows_impl(params, tokens, kv_k, kv_v, length, active):
                full = lm.Caches(
                    kv_k=kv_k, kv_v=kv_v, length=length,
                    mamba_conv=None, mamba_ssm=None, shared_k=None, shared_v=None,
                )
                logits, new = lm.decode_step(self.cfg, params, tokens, full)
                sel = active[None, :, None, None, None]
                return (
                    logits,
                    jnp.where(sel, new.kv_k, kv_k),
                    jnp.where(sel, new.kv_v, kv_v),
                    jnp.where(active, new.length, length),
                )

            self._decode_rows = jax.jit(_decode_rows_impl)
        else:
            self._extend = None
            self._extend_rows = None
            self._decode_rows = None
        # Decoded-run insertion: donate the cache buffers so XLA performs an
        # in-place dynamic_update_slice instead of copying the whole cache
        # per insertion.  Donation holds on every backend, so a caller that
        # reads a cache after passing it in fails the same way everywhere.
        donate = (0, 1)
        self._insert_run = jax.jit(kv_layout.insert_codec_run, donate_argnums=donate)
        self._insert_runs = jax.jit(
            kv_layout.insert_codec_runs,
            donate_argnums=donate,
            static_argnames=("run_tokens",),
        )
        # row-pool support (continuous admission): suspend/resume a row's
        # realized prefix and recycle freed rows in place
        self._restore_row = jax.jit(kv_layout.restore_row, donate_argnums=donate)
        self._reset_rows = jax.jit(kv_layout.reset_rows, donate_argnums=donate)
        if self._extend is not None:
            # gather -> compact prefill_extend -> scatter back: coalesced
            # TEXT recompute that only computes the participating rows
            # (cache buffers donated so the row scatter updates in place)
            gather_donate = (2, 3)

            def _extend_gather_impl(params, tokens, kv_k, kv_v, length, rows):
                sub = lm.Caches(
                    kv_k=kv_k[:, rows], kv_v=kv_v[:, rows], length=length[rows],
                    mamba_conv=None, mamba_ssm=None, shared_k=None, shared_v=None,
                )
                logits, sub = lm.prefill_extend(self.cfg, params, tokens, sub)
                return (
                    logits,
                    kv_k.at[:, rows].set(sub.kv_k),
                    kv_v.at[:, rows].set(sub.kv_v),
                    length.at[rows].set(sub.length),
                )

            self._extend_gather = jax.jit(
                _extend_gather_impl, donate_argnums=gather_donate
            )
        else:
            self._extend_gather = None

    # ------------------------------------------------------------------
    # Paper interfaces
    # ------------------------------------------------------------------

    def calculate_kv(self, batch: Dict[str, jnp.ndarray]) -> Tuple[jnp.ndarray, Caches]:
        """Prefill the context; returns (last logits, caches)."""
        return self._prefill(self.params, batch, pad_to=self.capacity)

    def generate_with_kv(
        self, caches: Caches, first_token: jnp.ndarray, n_tokens: int
    ) -> np.ndarray:
        """Greedy generation from a (possibly codec-decoded) KV cache.

        first_token: (B,) int32.  Returns (B, n_tokens) generated ids.
        """
        tok = first_token[:, None].astype(jnp.int32)
        out = []
        for _ in range(n_tokens):
            logits, caches = self._decode(self.params, tok, caches)
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
            out.append(np.asarray(tok[:, 0]))
        return np.stack(out, axis=1)

    def logits_with_kv(
        self, caches: Caches, tokens: np.ndarray
    ) -> Tuple[np.ndarray, Caches]:
        """Teacher-forced stepping: returns per-step logits (B, T, V).

        Used by the quality benchmarks (perplexity / argmax-agreement of
        compressed vs. uncompressed caches).
        """
        outs = []
        for t in range(tokens.shape[1]):
            logits, caches = self._decode(
                self.params, jnp.asarray(tokens[:, t : t + 1], jnp.int32), caches
            )
            outs.append(np.asarray(logits[:, 0], dtype=np.float32))
        return np.stack(outs, axis=1), caches

    # ------------------------------------------------------------------
    # Streamer support
    # ------------------------------------------------------------------

    def prefill_extend(
        self, tokens: jnp.ndarray, caches: Caches
    ) -> Tuple[jnp.ndarray, Caches]:
        """Text-chunk recompute on top of loaded KV (fallback config)."""
        if self._extend is None:
            raise ValueError(f"no chunked prefill for family {self.cfg.family}")
        return self._extend(self.params, tokens, caches)

    def empty_caches(self, batch: int) -> Caches:
        return kv_layout.alloc_caches(self.cfg, batch, self.capacity)

    def decode_to_cache(self, caches: Caches, kv_new, start: int) -> Caches:
        """Write a decoded codec run ``(L, 2, T, C)`` into the serving cache.

        Fast path for ``streamer.materialize``: one jitted, donated-buffer
        ``dynamic_update_slice`` per run of decoded chunks — the run tensor
        (``codec.decode_chunks`` output) never leaves the device and the
        cache is not copied per chunk.
        """
        k, v, ln = self._insert_run(
            caches.kv_k, caches.kv_v, caches.length, jnp.asarray(kv_new),
            jnp.int32(start),
        )
        return caches._replace(kv_k=k, kv_v=v, length=ln)

    # ------------------------------------------------------------------
    # Concurrent-scheduler support (batch-of-requests cache)
    # ------------------------------------------------------------------

    def insert_runs(
        self,
        caches: Caches,
        kv_new,  # (L, 2, sum_T, C): all runs' decoded tokens, concat order
        rows: Sequence[int],  # cache row per run (distinct)
        starts: Sequence[int],  # token offset per run
        run_tokens: Sequence[int],  # token count per run
    ) -> Caches:
        """Land several requests' decoded runs in one batched dispatch.

        ``kv_new`` is the cross-request concat from
        ``codec.decode_chunk_runs``; run ``i`` (spanning ``run_tokens[i]``
        tokens of it) is written into cache row ``rows[i]`` at token offset
        ``starts[i]`` via one vmap'd per-row-offset ``dynamic_update_slice``
        over the whole batch — replacing one ``decode_to_cache`` dispatch
        per request per run.  Rows not named keep their contents
        byte-identically.  Only run geometry is static for jit; row
        assignment and offsets are data.
        """
        if not (len(rows) == len(starts) == len(run_tokens)):
            raise ValueError(
                f"insert_runs: {len(rows)} rows, {len(starts)} starts, "
                f"{len(run_tokens)} runs — one of each per run required"
            )
        if len(set(rows)) != len(rows):
            raise ValueError(f"insert_runs: duplicate cache rows in {rows}")
        n_rows = caches.kv_k.shape[1]
        if any(not 0 <= int(r) < n_rows for r in rows):
            # out of range would hit XLA's silent scatter-drop inside jit
            raise ValueError(
                f"insert_runs: rows {list(rows)} out of range for a "
                f"{n_rows}-row cache"
            )
        t_max = max(run_tokens)
        if t_max > self.capacity:
            raise ValueError(
                f"run of {t_max} tokens exceeds cache capacity {self.capacity}"
            )
        for s, t in zip(starts, run_tokens):
            # the insert kernel's shifted-window merge masks out-of-capacity
            # positions rather than writing them, so an overhanging run
            # would silently drop tokens while still advancing length
            if int(s) + int(t) > self.capacity:
                raise ValueError(
                    f"run of {t} tokens at offset {s} overhangs cache "
                    f"capacity {self.capacity}"
                )
        with TraceAnnotation("engine.insert_runs", n_runs=len(rows)):
            k, v, ln = self._insert_runs(
                caches.kv_k, caches.kv_v, caches.length, jnp.asarray(kv_new),
                jnp.asarray(list(rows), jnp.int32),
                jnp.asarray(list(starts), jnp.int32),
                run_tokens=tuple(int(t) for t in run_tokens),
            )
        return caches._replace(kv_k=k, kv_v=v, length=ln)

    # ------------------------------------------------------------------
    # Row-pool support (continuous admission / preemption)
    # ------------------------------------------------------------------

    def save_row(self, caches: Caches, row: int, n_tokens: int):
        """Snapshot the first ``n_tokens`` realized tokens of one cache row
        (suspending a preempted session).  The snapshot owns its buffers, so
        the pool cache may be freely recycled/donated afterwards."""
        n_rows = caches.kv_k.shape[1]
        if not 0 <= int(row) < n_rows:
            raise ValueError(
                f"save_row: row {row} out of range for a {n_rows}-row cache"
            )
        if not 0 <= int(n_tokens) <= self.capacity:
            raise ValueError(
                f"save_row: {n_tokens} tokens out of range for capacity "
                f"{self.capacity}"
            )
        return kv_layout.save_row(caches, int(row), int(n_tokens))

    def restore_row(self, caches: Caches, snapshot, row: int) -> Caches:
        """Re-insert a suspended session's snapshot into (possibly another)
        ``row`` of the pool cache: one donated-buffer write, then the row
        reads exactly as it did at suspension (length included)."""
        n_rows = caches.kv_k.shape[1]
        if not 0 <= int(row) < n_rows:
            raise ValueError(
                f"restore_row: row {row} out of range for a {n_rows}-row cache"
            )
        if snapshot.n_tokens > self.capacity:
            raise ValueError(
                f"restore_row: snapshot of {snapshot.n_tokens} tokens exceeds "
                f"cache capacity {self.capacity}"
            )
        k, v, ln = self._restore_row(
            caches.kv_k, caches.kv_v, caches.length,
            snapshot.kv_k, snapshot.kv_v, jnp.int32(row),
        )
        return caches._replace(kv_k=k, kv_v=v, length=ln)

    def reset_rows(self, caches: Caches, rows: Sequence[int]) -> Caches:
        """Zero recycled rows (KV and length) before new tenants take them —
        a recycled row must be indistinguishable from a fresh cache's row."""
        n_rows = caches.kv_k.shape[1]
        if any(not 0 <= int(r) < n_rows for r in rows):
            raise ValueError(
                f"reset_rows: rows {list(rows)} out of range for a "
                f"{n_rows}-row cache"
            )
        k, v, ln = self._reset_rows(
            caches.kv_k, caches.kv_v, caches.length,
            jnp.asarray(list(rows), jnp.int32),
        )
        return caches._replace(kv_k=k, kv_v=v, length=ln)

    def prefill_extend_rows(
        self, tokens: jnp.ndarray, caches: Caches, widths
    ) -> Tuple[jnp.ndarray, Caches]:
        """Coalesced TEXT recompute: one padded, width-masked batched
        ``prefill_extend`` over the batch-of-requests cache.

        ``tokens`` is (B, Tc) with each participating row's text chunk (rows
        with ``widths[b] == 0`` carry padding and are untouched — garbage
        logits, no cache write, no length advance).  Each row writes at its
        *own* ``caches.length[b]`` offset.
        """
        if self._extend_rows is None:
            raise ValueError(f"no chunked prefill for family {self.cfg.family}")
        return self._extend_rows(
            self.params, tokens, caches, jnp.asarray(widths, jnp.int32)
        )

    def prefill_extend_gather(
        self, tokens: jnp.ndarray, caches: Caches, rows
    ) -> Tuple[jnp.ndarray, Caches]:
        """Compact coalesced TEXT recompute for a *subset* of cache rows.

        Gathers rows ``rows`` of the batch-of-requests cache into a
        sub-batch, runs the plain full-width ``prefill_extend`` on it
        (``tokens`` is (len(rows), Tc), one text chunk per gathered row),
        and scatters the updated rows back.  Complements
        :meth:`prefill_extend_rows`: same semantics, but compute scales with
        the participating rows instead of the full batch — the scheduler
        picks this when only a few sessions recompute in a round.  Row
        membership is data (no retrace per row set); only (k, Tc) shape the
        jit signature.
        """
        if self._extend_gather is None:
            raise ValueError(f"no chunked prefill for family {self.cfg.family}")
        n_rows = caches.kv_k.shape[1]
        if any(not 0 <= int(r) < n_rows for r in rows):
            # out of range would clamp inside jit and corrupt the last row
            raise ValueError(
                f"prefill_extend_gather: rows {list(rows)} out of range for "
                f"a {n_rows}-row cache"
            )
        logits, k, v, ln = self._extend_gather(
            self.params, tokens, caches.kv_k, caches.kv_v, caches.length,
            jnp.asarray(list(rows), jnp.int32),
        )
        return logits, caches._replace(kv_k=k, kv_v=v, length=ln)

    def decode_step_rows(
        self, tokens: jnp.ndarray, caches: Caches, active
    ) -> Tuple[jnp.ndarray, Caches]:
        """Stacked generation step: all generating rows' next token in one
        forward over the batch-of-requests cache.

        ``tokens`` is (B, 1) with each generating row's current token (rows
        with ``active[b] == False`` carry padding); ``active`` is (B,) bool.
        Each active row attends over its own realized prefix (per-row
        ``caches.length[b]`` offsets), writes its token's KV at that offset,
        and advances its length by one; inactive rows' KV and length are
        bit-preserved.  Returns (logits (B, 1, V), caches) — inactive rows'
        logits are garbage, mirroring :meth:`prefill_extend_rows`.

        Active rows must have ``length < capacity`` before the step (the
        written token needs a slot); callers validate this host-side when
        scheduling generation.
        """
        if self._decode_rows is None:
            raise ValueError(f"no cached generation for family {self.cfg.family}")
        n_rows = caches.kv_k.shape[1]
        tokens = jnp.asarray(tokens, jnp.int32)
        if tokens.shape != (n_rows, 1):
            raise ValueError(
                f"decode_step_rows: tokens shape {tokens.shape} != "
                f"({n_rows}, 1) for a {n_rows}-row cache"
            )
        active = jnp.asarray(active, bool)
        if active.shape != (n_rows,):
            raise ValueError(
                f"decode_step_rows: active shape {active.shape} != "
                f"({n_rows},) for a {n_rows}-row cache"
            )
        logits, k, v, ln = self._decode_rows(
            self.params, tokens, caches.kv_k, caches.kv_v, caches.length, active
        )
        return logits, caches._replace(kv_k=k, kv_v=v, length=ln)

    # ------------------------------------------------------------------
    # Cost model hooks (used by the streaming simulator)
    # ------------------------------------------------------------------

    def prefill_flops(self, n_tokens: int, kv_prefix: int = 0) -> float:
        """Approximate forward FLOPs to prefill ``n_tokens`` given a prefix."""
        cfg = self.cfg
        L = cfg.dec_layers if cfg.family == "encdec" else cfg.n_layers
        d, ff = cfg.d_model, cfg.d_ff
        if cfg.family == "moe":
            ff_eff = ff * (cfg.moe_topk + cfg.n_shared_experts)
        else:
            ff_eff = ff
        per_tok = 2 * (
            d * (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.d_head  # qkv
            + cfg.n_heads * cfg.d_head * d  # out proj
            + 3 * d * ff_eff  # gated mlp
        )
        attn = 2 * 2 * cfg.n_heads * cfg.d_head * (
            n_tokens * kv_prefix + n_tokens * (n_tokens + 1) // 2
        )
        return float(L) * (per_tok * n_tokens + attn) + 2.0 * n_tokens * d * cfg.vocab_size
