#!/usr/bin/env python3
"""Smoke run of the CacheGen serving path on a TPU.

Drives store -> stream -> engine -> scheduler -> generate once, in one
process, at the published width of smollm-360m (32 layers, d_model 960,
15 heads over 5 KV heads, vocab 49152) with random weights drawn from
``--seed``:

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # row-sharded engine on a four-chip host

One chip: a seeded MarkovLM context of 3072 tokens is prefilled, the codec
tables are profiled on its KV, and every level is stored in 256-token
chunks.  Four requests then load it through the continuous scheduler on two
cache rows (rows recycle) and generate 16 tokens each: one pinned to level 0
(the lossless kernel), two pinned to lossy levels (the dequant kernel), and
one adaptive request whose recompute is free, so Algorithm 1 recomputes
TEXT chunks.  Checks: the Pallas kernels equal their jnp twins on one chunk,
and the rANS decode equals the scan on it;
each cache equals the per-chunk ``materialize(fused=False)`` reference (bit
for bit at level 0, within the session tests' tolerance otherwise); each
request's scheduler tokens equal ``Engine.generate_with_kv`` on its cache;
every logit is finite; no request failed.

``--chips 4`` runs only what exists across chips: the ``ShardedEngine`` over
a four-device row mesh and the plain ``Engine`` on the same eight requests,
compared bit for bit (tokens and caches), after checking that the cache rows
land on all four devices.

Printed seconds are wall times of one cold run (compilation included),
smoke timings and not benchmark numbers.  The last line of standard output
is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
without a TPU, or when any check fails, the script exits non-zero and prints
no such line.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

ARCH = "smollm-360m"
CAPACITY = 4096
CTX_LEN = 3072  # 12 chunks of 256 tokens
MESH_CTX_LEN = 512  # the mesh comparison needs fewer chunks
CHUNK_TOKENS = 256
GEN_TOKENS = 16
LOSSY_TOL = dict(atol=2e-2, rtol=2e-2)  # tests/test_session.py


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str, passed: list) -> None:
    if not ok:
        raise SmokeFailure(what)
    passed.append(what)
    log(f"[check] {what}: ok")


_COMPILE_S = [0.0]


def _count_compile(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += duration


@contextlib.contextmanager
def phase(name: str):
    """Wall and backend-compile seconds of one phase (the body blocks on its
    own results, so the wall time covers the device work)."""
    c0, t0 = _COMPILE_S[0], time.perf_counter()
    yield
    log(
        f"[timing] {name}: wall {time.perf_counter() - t0:.3f} s, compile "
        f"{_COMPILE_S[0] - c0:.3f} s (smoke timing, not a benchmark number)"
    )


def _cache_np(caches, n_tokens: int):
    return [
        np.asarray(x[:, :, :n_tokens], np.float32)
        for x in (caches.kv_k, caches.kv_v)
    ]


def _reference_cache(ctx, engine, configs):
    """The per-chunk ``materialize(fused=False)`` cache for ``configs``."""
    from repro.streaming import BandwidthTrace, NetworkModel
    from repro.streaming.streamer import FetchPlan

    plan = ctx.streamer.stream(
        ctx.context_id, NetworkModel(BandwidthTrace.constant(10.0)),
        slo_s=1.0, recompute_s=lambda t, p: 0.0, fixed_level=0,
    )
    plan = FetchPlan(
        plan.context_id,
        dataclasses.replace(plan.result, configs=list(configs)),
        plan.metas,
    )
    return ctx.streamer.materialize(plan, engine, ctx.tokens, fused=False)


def _greedy(engine, caches, first: int, n: int, batch: int) -> list:
    """``generate_with_kv`` on one request's cache, replicated to ``batch``
    rows so the step runs at the scheduler's batch shape (a TPU picks its
    matmul tiling by shape, so bf16 logits can differ in the last bit
    between batch sizes and flip a near-tied argmax)."""
    import jax.numpy as jnp

    caches = caches._replace(
        kv_k=jnp.repeat(caches.kv_k, batch, axis=1),
        kv_v=jnp.repeat(caches.kv_v, batch, axis=1),
        length=jnp.repeat(caches.length, batch),
    )
    out = engine.generate_with_kv(caches, jnp.full((batch,), first, jnp.int32), n)
    return out[0].tolist()


def rans_check(blob: bytes, ct, passed: list) -> None:
    """One chunk's anchor and delta streams through ``rans.decode``'s path
    (the Pallas kernel on a TPU, reading the stacks' laid-out rows) and
    through the scan, with the tables and table offsets ``decode_chunks``
    uses: symbols, final states and final word pointers must agree bit for
    bit."""
    import jax

    from repro.core import bitstream, gop, rans
    from repro.core import codec as kvcodec

    parsed = [bitstream.unpack(blob)]
    h = parsed[0][0]
    level = int(h["level"])
    layout = gop.make_layout(int(h["n_tokens"]), int(h["group_size"]))
    t_idx = np.asarray(ct.table_idx)
    scan = jax.jit(rans._decode_scan, static_argnames=("precision", "n_sym"))
    streams = (
        ("anchors", "a", kvcodec._anchor_stack(ct),
         ct.anchor.n_tables if level == 0 else 0, layout.n_anchors),
        ("deltas", "d", kvcodec._delta_decode_stack(ct),
         kvcodec._delta_table_base(ct, level), layout.n_deltas),
    )
    for name, prefix, tab, base, n_sym in streams:
        w, n, x = kvcodec._stack_streams(parsed, [0], prefix)
        got = rans._decode_all(w, n, x, t_idx + base, tab, n_sym)
        want = scan(w, n, x, t_idx + base, tab.freqs, tab.cums, tab.slot2sym,
                    precision=tab.precision, n_sym=n_sym)
        same = [np.array_equal(np.asarray(g), np.asarray(v)) for g, v in zip(got, want)]
        laid_out = rans._row_blocks(t_idx + base, tab.rows) is not None
        log(f"[kernels] rANS {name} level {level} ({rans.decoder()}, rows laid out "
            f"once: {laid_out}): {len(w)} lanes x {n_sym} symbols, cap {w.shape[1]}; "
            f"symbols, state, ptr equal the scan: {same}")
        check(all(same), f"rANS {rans.decoder()} equals the scan "
              f"(level {level}, {name})", passed)


def kernel_check(ctx, lossy_level: int, passed: list) -> None:
    """Decode chunk 0 at level 0 and at a lossy level with the Pallas
    kernels and with their ``kernels/ref.py`` twins; the outputs must agree
    (bit for bit at level 0, to f32 rounding at the lossy level).  The rANS
    decode of the same chunks must equal the scan bit for bit."""
    import jax

    from repro.core import codec as kvcodec

    store = ctx.store
    for level in (0, lossy_level):
        blob = store.get_kv(ctx.context_id, 0, level)
        rans_check(blob, store.tables, passed)
        got = kvcodec.decode_chunks([blob], store.tables, use_pallas=True)
        want = kvcodec.decode_chunks([blob], store.tables, use_pallas=False)
        got, want = np.asarray(jax.block_until_ready(got)), np.asarray(want)
        diff = float(np.max(np.abs(got - want)))
        log(f"[kernels] level {level}: shape {got.shape} max |pallas - ref| {diff:.3g}")
        if level == 0:
            check(np.array_equal(got, want),
                  "lossless kernel equals kv_lossless_tokens_ref", passed)
        else:
            check(np.allclose(got, want, rtol=1e-6, atol=1e-6),
                  "dequant kernel equals kv_dequant_tokens_ref", passed)


def _pricing_line() -> None:
    """Say whether Algorithm 1 is priced with measured or default rates."""
    import jax

    from repro.streaming import calibration

    backend = jax.default_backend()
    rate = calibration.measured_decode_bytes_per_s(default=float("nan"))
    if math.isnan(rate):
        log(
            f"[smoke] Algorithm 1 priced with default rates: no codec report "
            f"exists for the {backend!r} backend (decode "
            f"{calibration.DEFAULT_DECODE_BYTES_PER_S:.3g} B/s, contention "
            f"factor(n) = n)"
        )
    else:
        log(f"[smoke] Algorithm 1 priced with measured decode {rate:.3g} B/s")


def serving_phase(
    cfg,
    *,
    ctx_len: int = CTX_LEN,
    chunk_tokens: int = CHUNK_TOKENS,
    capacity: int = CAPACITY,
    gen_tokens: int = GEN_TOKENS,
    seed: int = 0,
) -> dict:
    """The one-chip smoke: four requests on two rows, every chunk kind.

    Raises :class:`SmokeFailure` on the first failed check; returns the
    realized configs per request and the names of the checks that passed.
    """
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import build_engine, load_context
    from repro.serving.generation import GenerationSpec
    from repro.serving.scheduler import ContinuousScheduler, SessionRequest
    from repro.serving.session import ServeSession
    from repro.streaming import BandwidthTrace, NetworkModel
    from repro.streaming.adaptation import TEXT

    passed: list = []
    log(
        f"[smoke] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads / {cfg.n_kv_heads} kv heads x {cfg.d_head}, "
        f"vocab {cfg.vocab_size}; context {ctx_len} tokens in "
        f"{chunk_tokens}-token chunks, cache capacity {capacity}"
    )
    with phase("engine (random weights)"):
        engine = build_engine(cfg, capacity=capacity, seed=seed)
        jax.block_until_ready(engine.params)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(engine.params))
    log(f"[smoke] parameters: {n_params} ({n_params * 2 / 1e9:.3f} GB in bf16)")
    with phase("context (prefill, codec profile, store every level)"):
        ctx = load_context(
            engine, ctx_len=ctx_len, chunk_tokens=chunk_tokens, seed=seed
        )
    check(bool(np.isfinite(np.asarray(ctx.logits, np.float32)).all()),
          "prefill logits finite", passed)
    n_levels = ctx.store.tables.config.n_levels
    metas = ctx.store.meta(ctx.context_id)
    sizes = {lvl: sum(m.sizes[lvl] for m in metas) for lvl in range(n_levels)}
    log(f"[smoke] stored {len(metas)} chunks; bytes per level {sizes}")

    lossy = 2
    with phase("kernels vs jnp twins (one chunk)"):
        kernel_check(ctx, lossy, passed)

    _pricing_line()
    first = ctx.first_token
    trace = BandwidthTrace.constant(10.0)  # virtual link, Gbit/s
    plans = [  # (label, session knobs)
        ("level 0", dict(fixed_level=0)),
        (f"level {lossy}", dict(fixed_level=lossy)),
        ("adaptive, free recompute", dict(recompute_s=lambda t, p: 0.0)),
        (f"level {n_levels - 1}", dict(fixed_level=n_levels - 1)),
    ]
    requests = []
    for _, knobs in plans:
        kw = dict(slo_s=30.0, recompute_s=lambda t, p: 1e3)
        kw.update(knobs)
        requests.append(SessionRequest(
            ServeSession(ctx.streamer, engine, **kw), ctx.context_id,
            ctx.tokens, NetworkModel(trace),
            generation=GenerationSpec(n_tokens=gen_tokens, first_token=first),
        ))
    with phase("serve (continuous scheduler, 2 rows, 4 requests)"):
        out = ContinuousScheduler(engine, rows=2).run(requests)
        jax.block_until_ready([s.caches.kv_k for s in out.sessions])
    names = {TEXT: "TEXT"}
    configs = []
    for i, ((label, _), s, tl) in enumerate(zip(plans, out.sessions, out.timeline)):
        configs.append(list(s.configs))
        log(
            f"[req {i}] {label}: status={s.status} rows={tl.rows_used} "
            f"configs={[names.get(c, f'L{c}') for c in s.configs]} "
            f"tokens={tl.tokens_out}"
        )
    log(
        f"[smoke] scheduler: rounds={out.n_rounds} "
        f"decode_batches={out.n_decode_batches} "
        f"text_batches={out.n_text_batches} gen_steps={out.n_gen_steps}"
    )
    check(out.n_failed == 0 and all(s.status == "ok" for s in out.sessions),
          "no request failed", passed)
    flat = [c for cs in configs for c in cs]
    check(0 in flat and any(c > 0 for c in flat) and TEXT in flat
          and out.n_text_batches > 0,
          "level-0, lossy and TEXT chunks all ran", passed)
    used = [r for tl in out.timeline for r in tl.rows_used]
    check(len(used) == len(requests) and len(set(used)) < len(used),
          "cache rows recycled between requests", passed)

    with phase("oracles (reference caches, generate_with_kv, logits)"):
        for i, s in enumerate(out.sessions):
            ref = _reference_cache(ctx, engine, s.configs)
            got, want = _cache_np(s.caches, ctx_len), _cache_np(ref, ctx_len)
            diff = max(float(np.max(np.abs(a - b))) for a, b in zip(got, want))
            log(f"[req {i}] max |cache - reference| {diff:.3g}")
            check(int(s.caches.length[0]) == ctx_len,
                  f"req {i} cache length {ctx_len}", passed)
            if all(c == 0 for c in s.configs):
                check(all(np.array_equal(a, b) for a, b in zip(got, want)),
                      f"req {i} level-0 cache equals the reference bit for bit",
                      passed)
            else:
                check(all(np.allclose(a, b, **LOSSY_TOL) for a, b in zip(got, want)),
                      f"req {i} cache within tolerance of the reference", passed)
            toks = out.timeline[i].tokens_out
            check(toks == _greedy(engine, s.caches, first, gen_tokens, out.n_rows),
                  f"req {i} scheduler tokens equal generate_with_kv", passed)
            # teacher-forced logits along the scheduler's tokens
            logits, _ = engine.logits_with_kv(
                s.caches, np.asarray([[first] + toks[:-1]], np.int32)
            )
            check(bool(np.isfinite(logits).all()),
                  f"req {i} decode logits finite", passed)
    return dict(configs=configs, checks=passed)


def mesh_phase(
    cfg,
    *,
    n_chips: int = 4,
    ctx_len: int = MESH_CTX_LEN,
    chunk_tokens: int = CHUNK_TOKENS,
    capacity: int = CAPACITY,
    gen_tokens: int = GEN_TOKENS,
    seed: int = 0,
) -> dict:
    """ShardedEngine over ``n_chips`` devices vs the plain Engine: eight
    requests on eight rows, tokens and caches compared bit for bit."""
    import jax

    from repro.launch.mesh import make_serving_mesh
    from repro.launch.serve import build_engine, load_context
    from repro.serving.generation import GenerationSpec
    from repro.serving.mesh_engine import ShardedEngine
    from repro.serving.scheduler import ContinuousScheduler, SessionRequest
    from repro.serving.session import ServeSession
    from repro.streaming import BandwidthTrace, NetworkModel
    from repro.streaming.adaptation import TEXT
    from repro.streaming.pipeline import ContentionModel

    passed: list = []
    rows = 2 * n_chips
    if len(jax.devices()) < n_chips:
        raise SmokeFailure(f"need {n_chips} devices, JAX sees {len(jax.devices())}")
    with phase("engines (plain + sharded)"):
        engine = build_engine(cfg, capacity=capacity, seed=seed)
        sharded = ShardedEngine(
            cfg, engine.params, capacity, mesh=make_serving_mesh(n_chips)
        )
        jax.block_until_ready(engine.params)
    with phase("context"):
        ctx = load_context(
            engine, ctx_len=ctx_len, chunk_tokens=chunk_tokens, seed=seed
        )
    caches = sharded.empty_caches(rows)
    placed = {
        sh.device.id: sh.data.shape[1] for sh in caches.kv_k.addressable_shards
    }
    log(f"[mesh] cache rows per device id: {placed}")
    check(len(placed) == n_chips and set(placed.values()) == {rows // n_chips},
          f"cache rows spread over {n_chips} devices", passed)
    del caches

    levels = [0, 2, None, 4]  # None: adaptive with free recompute (TEXT)
    ideal = ContentionModel({1: 1.0, 2: 1.0})  # same virtual clock for both

    def run(eng, n_rows):
        reqs = []
        for i in range(rows):
            lvl = levels[i % len(levels)]
            kw = (dict(recompute_s=lambda t, p: 0.0) if lvl is None
                  else dict(recompute_s=lambda t, p: 1e3, fixed_level=lvl))
            reqs.append(SessionRequest(
                ServeSession(ctx.streamer, eng, slo_s=30.0, **kw),
                ctx.context_id, ctx.tokens,
                NetworkModel(BandwidthTrace.constant(10.0)),
                generation=GenerationSpec(gen_tokens, ctx.first_token),
            ))
        out = ContinuousScheduler(eng, rows=n_rows, contention=ideal).run(reqs)
        jax.block_until_ready([s.caches.kv_k for s in out.sessions])
        return out

    # the plain engine runs each device's batch shape (rows per shard): a
    # TPU picks matmul tiling by shape, so equal shapes are what make bf16
    # results comparable bit for bit; requests queue for its rows instead
    per_shard = rows // n_chips
    with phase(f"serve on the plain engine ({per_shard} rows, 1 device)"):
        plain = run(engine, per_shard)
    with phase(f"serve on the sharded engine ({rows} rows, {n_chips} devices)"):
        shard = run(sharded, rows)
    shards_used = {
        sharded.shard_of(r, rows) for tl in shard.timeline for r in tl.rows_used
    }
    check(shards_used == set(range(n_chips)),
          f"requests served on all {n_chips} row shards", passed)
    check(plain.n_failed == 0 and shard.n_failed == 0, "no request failed", passed)
    check(any(TEXT in s.configs for s in shard.sessions),
          "TEXT recompute ran on the sharded engine", passed)
    for i in range(rows):
        a, b = plain.sessions[i], shard.sessions[i]
        log(
            f"[req {i}] configs={a.configs} rows plain={plain.timeline[i].rows_used} "
            f"sharded={shard.timeline[i].rows_used} tokens={shard.timeline[i].tokens_out}"
        )
        check(a.configs == b.configs, f"req {i} same decisions", passed)
        check(plain.timeline[i].tokens_out == shard.timeline[i].tokens_out,
              f"req {i} sharded tokens equal plain", passed)
        same = all(
            np.array_equal(x, y)
            for x, y in zip(_cache_np(a.caches, ctx_len),
                            _cache_np(b.caches, ctx_len))
        )
        check(same and int(a.caches.length[0]) == int(b.caches.length[0]),
              f"req {i} sharded cache equals plain bit for bit", passed)
    return dict(checks=passed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the context")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: compare the row-sharded engine on four chips "
                         "with the plain engine, and nothing else")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); "
              f"this smoke runs only on a TPU", file=sys.stderr)
        return 2
    device = dict(platform=platform, kind=devices[0].device_kind,
                  count=len(devices))
    log(f"[smoke] device kind={device['kind']} count={device['count']}")
    try:
        from repro.configs import registry
        from repro.launch.compile_cache import enable_compilation_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not importable next to this "
              f"script ({e})", file=sys.stderr)
        return 2
    log(f"[smoke] compilation cache: {enable_compilation_cache()}")
    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    cfg = registry.get(ARCH)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            mesh_phase(cfg, n_chips=4, seed=args.seed)
        else:
            serving_phase(cfg, seed=args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    log(f"[timing] total: wall {time.perf_counter() - t0:.3f} s, compile "
        f"{_COMPILE_S[0]:.3f} s (smoke timing, not a benchmark number)")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
