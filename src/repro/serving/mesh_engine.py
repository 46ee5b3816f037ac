"""Mesh-sharded serving engine: the batch-of-requests cache across devices.

One :class:`~repro.serving.engine.Engine` binds the whole serving path to a
single device, so row capacity and aggregate decode throughput stop at one
device's memory and FLOPs.  :class:`ShardedEngine` partitions the
batch-of-requests cache's *row* axis over a mesh axis (blocked layout —
global row ``r`` lives on shard ``r // (B/S)`` at local row ``r % (B/S)``)
and runs the four hot primitives of the serving loop — run insertion
(after the host-side ``codec.decode_chunk_runs``), coalesced TEXT
recompute (``prefill_extend_rows``), stacked generation
(``decode_step_rows``), and the row-pool reset/restore — under
``shard_map``, with partition specs derived from the logical-axis rule set
(``models.sharding.use_rules`` / ``logical_to_spec``, logical axis
``"cache_rows"``).

Because every primitive is row-parallel (each row attends over its own
prefix; the inactive-row where-merge, the window merge of
``insert_codec_runs``, and save/restore/reset are all row-local), the
shard bodies are collective-free and perform exactly the unsharded
kernels' per-row arithmetic — which is what keeps a mesh of 1 bit-identical
to the plain Engine, and per-request caches/tokens bit-identical at any
shard count.  ``save_row`` needs no sharded variant: slicing a
``NamedSharding`` array is addressable from the host.

Row counts must divide by the shard count on the sharded path; the
schedulers size their pool cache via ``Engine.cache_rows``.  Calls whose
cache batch is *not* divisible (e.g. a batch-1 ``ServeSession`` cache)
transparently fall back to the inherited single-device callables, so the
single-session path keeps working unchanged on a sharded engine.

``kv_heads``-along-``model`` tensor parallelism inside each row is left
replicated here (it needs a psum over the attention out-projection —
tracked as a ROADMAP follow-on); the mesh's win is rows, decode width, and
per-shard fetch bandwidth.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import lm, sharding
from repro.models.lm import Caches
from repro.serving import kv_layout
from repro.serving.engine import Engine

__all__ = ["ShardedEngine"]


class ShardedEngine(Engine):
    """Engine whose batch-of-requests cache rows are sharded over a mesh.

    ``mesh`` must carry the axis the ``"cache_rows"`` rule maps to (the
    ``"data"`` axis of ``launch.mesh.make_serving_mesh`` /
    ``make_test_mesh``); ``rules`` overlays the default logical-axis rule
    set.  With a one-device mesh the engine is bit-identical to the plain
    :class:`Engine` through every entry point (held by
    tests/test_mesh_serving.py).
    """

    def __init__(
        self,
        cfg: ArchConfig,
        params,
        cache_capacity: int = 4096,
        *,
        mesh: Mesh,
        rules: Optional[Dict[str, object]] = None,
    ):
        super().__init__(cfg, params, cache_capacity)
        if self._decode_rows is None or self._extend_rows is None:
            raise ValueError(
                f"ShardedEngine needs a KV-cache attention family, got "
                f"{cfg.family!r}"
            )
        self.mesh = mesh
        # Partition specs come from the logical rule set, so re-mapping
        # "cache_rows" re-distributes the whole serving path without
        # touching this module.
        with sharding.use_rules(mesh, rules):
            self._cache_spec = sharding.logical_to_spec(
                ("layers", "cache_rows", "kv_seq", "kv_heads", "head_dim")
            )
            self._rows_spec = sharding.logical_to_spec(("cache_rows",))
        part = self._rows_spec[0]
        axes = () if part is None else (
            (part,) if isinstance(part, str) else tuple(part)
        )
        if len(axes) > 1:
            raise ValueError(
                f"cache_rows maps to {axes} on this mesh; row sharding "
                f"supports exactly one mesh axis — overlay a rule like "
                f"{{'cache_rows': 'data'}}"
            )
        self.row_axis: Optional[str] = axes[0] if axes else None
        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.n_shards = int(axis_sizes[self.row_axis]) if self.row_axis else 1

        ax = self.row_axis
        c_spec = self._cache_spec  # (L, B, cap, Hkv, Dh)
        r_spec = self._rows_spec  # (B,)
        rows2 = P(*(list(r_spec) + [None]))  # (B, 1) tokens / (B, Tc) texts
        logits3 = P(*(list(r_spec) + [None, None]))  # (B, T, V)
        rep = P()

        def _sm(body, in_specs, out_specs):
            return jax.shard_map(
                body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False,
            )

        # --- decode_step_rows: stacked generation step per shard ---------
        def _decode_rows_body(params_, tokens, kv_k, kv_v, length, active):
            full = lm.Caches(
                kv_k=kv_k, kv_v=kv_v, length=length,
                mamba_conv=None, mamba_ssm=None, shared_k=None, shared_v=None,
            )
            logits, new = lm.decode_step(self.cfg, params_, tokens, full)
            sel = active[None, :, None, None, None]
            return (
                logits,
                jnp.where(sel, new.kv_k, kv_k),
                jnp.where(sel, new.kv_v, kv_v),
                jnp.where(active, new.length, length),
            )

        sm_decode_rows = jax.jit(_sm(
            _decode_rows_body,
            in_specs=(rep, rows2, c_spec, c_spec, r_spec, r_spec),
            out_specs=(logits3, c_spec, c_spec, r_spec),
        ))

        # --- prefill_extend_rows: width-masked TEXT recompute per shard --
        def _extend_rows_body(params_, tokens, kv_k, kv_v, length, widths):
            caches = lm.Caches(
                kv_k=kv_k, kv_v=kv_v, length=length,
                mamba_conv=None, mamba_ssm=None, shared_k=None, shared_v=None,
            )
            logits, new = lm.prefill_extend(
                self.cfg, params_, tokens, caches, widths=widths
            )
            return logits, new.kv_k, new.kv_v, new.length

        sm_extend_leaves = _sm(
            _extend_rows_body,
            in_specs=(rep, rows2, c_spec, c_spec, r_spec, r_spec),
            out_specs=(logits3, c_spec, c_spec, r_spec),
        )

        def _extend_rows_outer(params_, tokens, caches, widths):
            logits, k, v, ln = sm_extend_leaves(
                params_, tokens, caches.kv_k, caches.kv_v, caches.length,
                widths,
            )
            return logits, caches._replace(kv_k=k, kv_v=v, length=ln)

        sm_extend_rows = jax.jit(_extend_rows_outer)

        # --- insert_runs: decoded-run landing per shard ------------------
        @functools.partial(jax.jit, static_argnames=("run_tokens",))
        def sm_insert_runs(kv_k, kv_v, length, kv_new, rows, starts, *,
                           run_tokens):
            body = functools.partial(
                kv_layout.insert_codec_runs_local,
                run_tokens=run_tokens, axis=ax,
            )
            return _sm(
                body,
                in_specs=(c_spec, c_spec, r_spec, rep, rep, rep),
                out_specs=(c_spec, c_spec, r_spec),
            )(kv_k, kv_v, length, kv_new, rows, starts)

        # --- row-pool restore / reset ------------------------------------
        def sm_restore_impl(kv_k, kv_v, length, k_row, v_row, row):
            body = functools.partial(kv_layout.restore_row_local, axis=ax)
            return _sm(
                body,
                in_specs=(c_spec, c_spec, r_spec, rep, rep, rep),
                out_specs=(c_spec, c_spec, r_spec),
            )(kv_k, kv_v, length, k_row, v_row, row)

        sm_restore_row = jax.jit(sm_restore_impl)

        def sm_reset_impl(kv_k, kv_v, length, rows):
            body = functools.partial(kv_layout.reset_rows_local, axis=ax)
            return _sm(
                body,
                in_specs=(c_spec, c_spec, r_spec, rep),
                out_specs=(c_spec, c_spec, r_spec),
            )(kv_k, kv_v, length, rows)

        sm_reset_rows = jax.jit(sm_reset_impl)

        # Dispatch: sharded callables serve caches whose row count divides
        # into whole shards (every scheduler cache, via ``cache_rows``);
        # anything else — batch-1 ServeSession caches, replication
        # experiments — falls back to the inherited single-device path.
        def _pick(base_fn, sharded_fn, batch_of):
            if self.n_shards == 1 and self.row_axis is None:
                return sharded_fn

            def call(*args, **kwargs):
                b = batch_of(*args, **kwargs)
                fn = sharded_fn if b % self.n_shards == 0 else base_fn
                return fn(*args, **kwargs)

            return call

        cache_b = lambda *a, **kw: a[2].shape[1]  # noqa: E731 (params, tokens, kv_k, ...)
        leading_b = lambda *a, **kw: a[0].shape[1]  # noqa: E731 (kv_k, ...)
        self._decode_rows = _pick(self._decode_rows, sm_decode_rows, cache_b)
        self._extend_rows = _pick(
            self._extend_rows, sm_extend_rows,
            lambda params_, tokens, caches, widths: caches.kv_k.shape[1],
        )
        self._insert_runs = _pick(self._insert_runs, sm_insert_runs, leading_b)
        self._restore_row = _pick(self._restore_row, sm_restore_row, leading_b)
        self._reset_rows = _pick(self._reset_rows, sm_reset_rows, leading_b)

    # ------------------------------------------------------------------

    def shard_of(self, row: int, batch: int) -> int:
        """Shard owning global ``row`` of a ``batch``-row sharded cache."""
        return int(row) // (int(batch) // self.n_shards)

    def empty_caches(self, batch: int) -> Caches:
        """A fresh batch-of-requests cache, row-sharded over the mesh.

        ``batch`` must divide into whole shards for the sharded layout
        (schedulers round up via :meth:`cache_rows`); other batches come
        back unsharded, served by the fallback single-device callables.
        """
        caches = kv_layout.alloc_caches(self.cfg, batch, self.capacity)
        if self.n_shards == 1 or batch % self.n_shards:
            return caches
        sh_cache = NamedSharding(self.mesh, self._cache_spec)
        sh_rows = NamedSharding(self.mesh, self._rows_spec)
        return caches._replace(
            kv_k=jax.device_put(caches.kv_k, sh_cache),
            kv_v=jax.device_put(caches.kv_v, sh_cache),
            length=jax.device_put(caches.length, sh_rows),
        )
