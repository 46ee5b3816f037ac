"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Brings up the engine for an architecture at its published widths
(``--arch smollm-360m``; a ``-tiny`` suffix selects the reduced
same-family config, e.g. ``--arch smollm-360m-tiny`` on a CPU), stores a
context pool
through the CacheGen streamer, then serves a request loop — each request is
a live closed-loop :class:`~repro.serving.session.ServeSession`: per chunk
it measures realized throughput from the trace-driven fetch, picks the next
streaming configuration (Algorithm 1), decodes fetched bitstreams through
the fused batched path and recomputes TEXT chunks for real, then generates.
``--check-sim`` cross-checks every session's per-chunk decisions against the
offline simulator on the same trace (the differential invariant that
tests/test_session.py enforces).

``--concurrency N`` (N > 1) serves the requests in waves of N concurrent
context loads on the one shared engine via
:class:`~repro.serving.scheduler.ConcurrentScheduler` — each request keeps
its own trace/policy/clock, while decodes, cache insertions and TEXT
recomputes are batched across requests, and per-session compute charges are
stretched by the measured contention model.

``--arrivals`` switches from closed waves to *open-loop* serving (ISSUE 5):
requests arrive over virtual time (``poisson:RATE`` draws seeded
exponential inter-arrivals at RATE requests/s; ``trace:FILE`` reads one
ascending arrival time per line) and are admitted by the
:class:`~repro.serving.scheduler.ContinuousScheduler` the moment one of
``--rows`` cache rows frees — TTFT then includes queueing delay from
arrival.  ``--preempt`` additionally lets a waiting arrival evict a live
session whose in-flight fetch is known to land past its SLO deadline (plus
``--preempt-margin``): the straggler's fetch handle is cancelled, its
realized rows are suspended into a snapshot, and it resumes on the next
free row.

``--store tiered`` (ISSUE 7) swaps the flat context-keyed store for the
content-addressed :class:`~repro.streaming.storage.TieredKVStore`: chunks
are chain-hashed over the token prefix (shared document prefixes dedup
across contexts), a ``--hot-bytes``-bounded hot tier sits over the cold
tier (``--store-dir`` for an on-disk cold backend), eviction is level-aware
LRU with demotion write-through, and cold-tier hits report their slower
fetch timing to the session's throughput estimator.  Per-tier counters are
printed at exit; over ``--transport tcp`` the protocol carries the hash
keys and the server reads content-addressed.

``--transport`` picks the fetch path (ISSUE 4): ``sim`` (default) paces
real asynchronous store reads against the request's bandwidth trace —
simulator-differential, so ``--check-sim`` still holds; ``local`` reads the
store directly (wall-time link); ``tcp`` brings up an in-process
:class:`~repro.streaming.transport.TcpStoreServer` and fetches every
bitstream over an actual paced socket — the session's throughput estimator
then measures a real link, so ``--check-sim`` is meaningless there.
``--hedge-after S`` issues a duplicate fetch for any chunk still in flight
after S seconds; the loser is cancelled and its bytes are reported as
duplicate overhead.

``--fault-*`` injects seeded chaos into the fetch path (ISSUE 6):
``--fault-drop/-stall/-corrupt/-truncate`` perturb in-flight fetches (via
:class:`~repro.streaming.faults.FaultyTransport` on sim/local, server-side
on tcp; truncate delivers a valid prefix then severs), ``--fault-missing``
deletes store entries behind the readers' backs
(:func:`~repro.streaming.faults.with_faulty_backend`).  ``--retry N``
arms the session's :class:`~repro.streaming.transport.RetryPolicy`
(bounded attempts, backoff charged to the virtual clock, degrade to
coarser levels / TEXT unless ``--no-degrade``); without it, injected
faults reproduce the legacy crash-through behavior.

Byte-range resume (ISSUE 8): with ``--retry`` armed, failed/cancelled
fetches keep their checksum-verified byte prefix and the next attempt
refetches only the missing suffix (same level) or only the coarser delta
suffix on degrade (the level-invariant anchor composes bit-exactly);
``--no-resume`` restores PR 6 whole-blob retries for comparison.
``--replan-factor F`` additionally cancels an in-flight chunk on the sim
transport once its realized duration exceeds F× the live-estimate
prediction (§C.1 mid-chunk re-planning).  Per-request output then carries
``salvaged``/``resumes``/``replans`` next to the PR 6 fault counters, and
the aggregate lines reconcile salvaged + refetched == wire bytes.

``--generate N`` (ISSUE 9, open-loop only) keeps each request on its
engine row after the context load completes and decodes N output tokens
*inside* the scheduler's event loop: every virtual step stacks all ready
generating rows into one batched ``Engine.decode_step_rows`` dispatch, so
generation contends with in-flight context loads exactly as Algorithm 1
sees it (``ContentionModel.gen_factor``).  ``--gen-slo S`` attaches a
per-output-token latency SLO, ``--sample-seed`` switches greedy argmax to
seeded sampling, ``--gen-step-ms`` sets the uncontended virtual step cost.
Per-request output gains ``gen=``/``tpot_mean=``; the aggregate line adds
mean/p95 TPOT and total generated tokens/s.  ``--generate 0`` (default)
is load-only and bit-identical to the PR 8 open-loop path.
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Any, Callable, Optional

import numpy as np

SERVED_FAMILIES = ("dense", "moe", "vlm")


@dataclasses.dataclass
class ServedContext:
    """One stored context: its tokens, its prefill output and its store."""

    context_id: str
    tokens: np.ndarray  # (1, T) context token ids
    logits: Any  # (1, 1, V) last-position prefill logits
    store: Any  # KVStore (or TieredKVStore) holding every level
    streamer: Any  # CacheGenStreamer over ``store``

    @property
    def first_token(self) -> int:
        """Greedy first output token: the prefill's TTFT artifact."""
        import jax.numpy as jnp

        return int(jnp.argmax(self.logits[0, -1]))


def build_engine(cfg, *, capacity: int, seed: int = 0):
    """Serving engine over random weights drawn from ``seed``."""
    import jax

    from repro.models import build
    from repro.serving.engine import Engine

    if cfg.family not in SERVED_FAMILIES:
        raise SystemExit(
            f"arch {cfg.name} is a {cfg.family!r} model; the serving path "
            f"streams KV caches of the attention families "
            f"{', '.join(SERVED_FAMILIES)}"
        )
    params = jax.jit(build(cfg).init_params)(jax.random.PRNGKey(seed))
    return Engine(cfg, params, cache_capacity=capacity)


def load_context(
    engine,
    *,
    ctx_len: int,
    chunk_tokens: int,
    seed: int = 0,
    make_store: Optional[Callable] = None,
) -> ServedContext:
    """Prefill a MarkovLM context drawn from ``seed``, profile the codec
    tables on its KV, and store every encoding level as context ``"ctx"``
    in ``chunk_tokens``-token chunks.  ``make_store(tables)`` builds the
    store (default: the flat ``KVStore``).
    """
    import jax.numpy as jnp

    from repro.core import codec as kvcodec
    from repro.data import MarkovLM
    from repro.serving.kv_layout import caches_to_codec_kv
    from repro.streaming import CacheGenStreamer, KVStore

    cfg = engine.cfg
    context_id = "ctx"
    rng = np.random.default_rng(seed)
    tokens = MarkovLM(vocab_size=cfg.vocab_size, seed=seed).sample(rng, ctx_len)[None]
    batch = {"tokens": jnp.asarray(tokens)}
    if cfg.family == "vlm":
        batch["patch_embeds"] = jnp.asarray(
            rng.normal(size=(1, cfg.n_prefix_tokens, cfg.frontend_dim)),
            jnp.float32,
        )
    logits, caches = engine.calculate_kv(batch)
    n_cached = ctx_len + (cfg.n_prefix_tokens if cfg.family == "vlm" else 0)
    kv = caches_to_codec_kv(caches, 0, n_cached)
    tables = kvcodec.profile([kv], kvcodec.CodecConfig(precision=11))
    store = (make_store or KVStore)(tables)
    store.store_kv(
        context_id, kv, chunk_tokens=chunk_tokens,
        # canonical token-chain hashing when the KV rows are 1:1 with
        # text tokens; a vlm's prefix rows aren't, so hash KV bytes there
        tokens=tokens[0].tolist() if tokens.shape[1] == n_cached else None,
    )
    return ServedContext(
        context_id=context_id, tokens=tokens, logits=logits,
        store=store, streamer=CacheGenStreamer(store, cfg),
    )


def _parse_arrivals(spec: str, n: int, seed: int):
    """``poisson:RATE`` (seeded exponential inter-arrivals) or
    ``trace:FILE`` (one ascending arrival time per line) -> n arrival
    instants on the virtual clock."""
    kind, _, val = spec.partition(":")
    if kind == "poisson":
        try:
            rate = float(val)
        except ValueError:
            raise SystemExit(f"--arrivals poisson:RATE needs a number, got {val!r}")
        if not rate > 0:  # also rejects nan
            raise SystemExit(f"--arrivals poisson rate must be > 0, got {rate}")
        rng = np.random.default_rng(seed)
        return np.cumsum(rng.exponential(1.0 / rate, size=n)).tolist()
    if kind == "trace":
        with open(val) as f:
            ts = [float(line) for line in f if line.strip()]
        if len(ts) < n:
            raise SystemExit(
                f"--arrivals trace:{val} has {len(ts)} arrivals, need {n}"
            )
        ts = ts[:n]
        if any(b < a for a, b in zip(ts, ts[1:])):
            raise SystemExit(f"--arrivals trace:{val} times must be ascending")
        return ts
    raise SystemExit("--arrivals must be poisson:RATE or trace:FILE")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m",
                    help="registry name at published widths; append -tiny "
                         "for the reduced same-family config")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--ctx-len", type=int, default=300)
    ap.add_argument("--slo-ms", type=float, default=250)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--fixed-level", type=int, default=None,
                    help="pin one encoding level (no adaptation baseline)")
    ap.add_argument("--max-run-tokens", type=int, default=None,
                    help="double-buffer granularity for fetch/decode overlap")
    ap.add_argument("--check-sim", action="store_true",
                    help="cross-check session decisions against the simulator")
    ap.add_argument("--concurrency", type=int, default=1,
                    help="serve requests in waves of N concurrent context "
                         "loads batched on the shared engine")
    ap.add_argument("--arrivals", default=None, metavar="SPEC",
                    help="open-loop serving instead of closed waves: "
                         "'poisson:RATE' draws seeded exponential "
                         "inter-arrivals at RATE requests/s on the virtual "
                         "clock; 'trace:FILE' reads one ascending arrival "
                         "time (seconds) per line.  Requests are admitted "
                         "to the --rows row pool as rows free up, so TTFT "
                         "includes queueing delay from arrival")
    ap.add_argument("--rows", type=int, default=None,
                    help="--arrivals: row-pool capacity (concurrent context "
                         "loads resident on the engine; default: "
                         "--concurrency)")
    ap.add_argument("--preempt", action="store_true",
                    help="--arrivals: let a waiting arrival preempt a live "
                         "session whose in-flight fetch is known to land "
                         "past its SLO deadline — the fetch is cancelled "
                         "and the session's realized rows suspend into a "
                         "snapshot until a row frees again")
    ap.add_argument("--preempt-margin", type=float, default=0.0, metavar="S",
                    help="extra SLO overshoot (seconds) a pending fetch "
                         "must incur before its session is preemptible")
    ap.add_argument("--arrival-seed", type=int, default=0,
                    help="seed for poisson:RATE arrival draws")
    ap.add_argument("--generate", type=int, default=0, metavar="N",
                    help="--arrivals: decode N output tokens per request on "
                         "the shared engine after its context load lands — "
                         "continuous batching: ready generating rows stack "
                         "into one decode_step_rows dispatch per virtual "
                         "step and contend with in-flight loads (0 = "
                         "load-only, bit-identical to the PR 8 path)")
    ap.add_argument("--gen-slo", type=float, default=None, metavar="S",
                    help="--generate: per-output-token latency SLO in "
                         "seconds (TPOT); EDF admission orders waiters by "
                         "start + SLO deadline")
    ap.add_argument("--sample-seed", type=int, default=None,
                    help="--generate: seeded softmax sampling instead of "
                         "greedy argmax (greedy stays bit-identical to the "
                         "generate_with_kv oracle)")
    ap.add_argument("--gen-step-ms", type=float, default=2.0,
                    help="--generate: uncontended virtual cost of one "
                         "stacked decode step (milliseconds)")
    ap.add_argument("--store", choices=("flat", "tiered"), default="flat",
                    help="storage layout: flat = context-keyed, keeps "
                         "everything forever; tiered = content-addressed "
                         "(chain-hashed token prefixes dedup across "
                         "contexts) with a capacity-bounded hot tier over "
                         "cold, level-aware LRU eviction, and cold-read "
                         "penalties fed to the throughput estimator")
    ap.add_argument("--hot-bytes", type=int, default=None, metavar="N",
                    help="--store tiered: hot-tier capacity in bytes "
                         "(default: never evict; 0 = everything cold)")
    ap.add_argument("--store-dir", default=None, metavar="DIR",
                    help="--store tiered: directory for the cold tier "
                         "(default: in-memory cold backend)")
    ap.add_argument("--transport", choices=("sim", "local", "tcp"),
                    default="sim",
                    help="fetch path: sim = trace-paced async reads "
                         "(simulator-differential), local = direct store "
                         "reads, tcp = real socket link to an in-process "
                         "store server")
    ap.add_argument("--hedge-after", type=float, default=None, metavar="S",
                    help="issue a duplicate (hedged) fetch for any chunk "
                         "still in flight after S seconds; the loser is "
                         "cancelled")
    ap.add_argument("--tcp-pace-gbps", type=float, default=0.2,
                    help="--transport tcp: server-side link pacing")
    ap.add_argument("--fault-drop", type=float, default=0.0, metavar="P",
                    help="probability a fetch attempt is dropped (link dies)")
    ap.add_argument("--fault-stall", type=float, default=0.0, metavar="P",
                    help="probability a fetch attempt stalls (Pareto tail)")
    ap.add_argument("--fault-corrupt", type=float, default=0.0, metavar="P",
                    help="probability a fetched payload is bit-flipped")
    ap.add_argument("--fault-truncate", type=float, default=0.0, metavar="P",
                    help="probability a fetch delivers a valid byte prefix "
                         "then severs (resumable with --retry)")
    ap.add_argument("--fault-missing", type=float, default=0.0, metavar="P",
                    help="probability a (chunk, level) entry is missing "
                         "from the store")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the deterministic fault plan")
    ap.add_argument("--fault-stall-scale", type=float, default=0.2,
                    metavar="S", help="injected stall scale (seconds)")
    ap.add_argument("--retry", type=int, default=0, metavar="N",
                    help="fault tolerance: total fetch attempts per chunk "
                         "level (0 = legacy crash-through on any failure)")
    ap.add_argument("--retry-backoff", type=float, default=0.02, metavar="S",
                    help="--retry: initial exponential backoff (seconds)")
    ap.add_argument("--retry-timeout", type=float, default=None, metavar="S",
                    help="--retry: per-attempt timeout (virtual seconds on "
                         "sim, wall seconds on local/tcp)")
    ap.add_argument("--no-degrade", action="store_true",
                    help="--retry: fail the session once retries are "
                         "exhausted instead of falling back to coarser "
                         "levels / TEXT recompute")
    ap.add_argument("--no-resume", action="store_true",
                    help="--retry: discard verified byte prefixes and "
                         "refetch whole blobs on retry (PR 6 baseline)")
    ap.add_argument("--replan-factor", type=float, default=None, metavar="F",
                    help="sim transport: cancel an in-flight chunk whose "
                         "realized duration exceeds F x the live-estimate "
                         "prediction, salvage the verified prefix, and "
                         "re-decide the remainder (mid-chunk re-planning)")
    args = ap.parse_args()
    if args.concurrency < 1:
        raise SystemExit("--concurrency must be >= 1")
    if args.generate < 0:
        raise SystemExit("--generate must be >= 0")
    if args.generate and args.arrivals is None:
        raise SystemExit(
            "--generate requires --arrivals (continuous batching lives in "
            "the open-loop scheduler); closed waves still generate post-hoc "
            "via --gen"
        )

    import jax.numpy as jnp

    from repro.configs import registry
    from repro.launch.compile_cache import enable_compilation_cache
    from repro.serving.session import ServeSession
    from repro.streaming import BandwidthTrace, NetworkModel
    from repro.streaming.adaptation import TEXT

    enable_compilation_cache()
    cfg = registry.get(args.arch)
    engine = build_engine(cfg, capacity=args.ctx_len + 32 + args.generate)
    make_store = None
    if args.store == "tiered":
        from repro.streaming import DirectoryBackend, TieredKVStore

        def make_store(tables):
            return TieredKVStore(
                tables,
                hot_bytes=args.hot_bytes,
                cold=DirectoryBackend(args.store_dir) if args.store_dir else None,
            )

    ctx = load_context(
        engine, ctx_len=args.ctx_len, chunk_tokens=max(args.ctx_len // 4, 50),
        make_store=make_store,
    )
    store, streamer, tokens, logits = ctx.store, ctx.streamer, ctx.tokens, ctx.logits
    rng = np.random.default_rng(0)  # link traces
    print(f"[serve] context stored: {store.storage_bytes('ctx')/1e3:.1f} KB all levels")

    # fetch path: sim (default, per-request trace pacing), local, or a real
    # in-process socket server with paced sends
    from repro.streaming import (
        FaultPlan,
        FaultyTransport,
        LocalTransport,
        RetryPolicy,
        SimTransport,
        TcpStoreServer,
        TcpTransport,
        with_faulty_backend,
    )

    fault_plan = None
    if (args.fault_drop or args.fault_stall or args.fault_corrupt
            or args.fault_truncate or args.fault_missing):
        fault_plan = FaultPlan(
            seed=args.fault_seed,
            drop_p=args.fault_drop,
            stall_p=args.fault_stall,
            corrupt_p=args.fault_corrupt,
            truncate_p=args.fault_truncate,
            missing_p=args.fault_missing,
            stall_scale_s=args.fault_stall_scale,
        )
        print(f"[serve] fault plan armed: {fault_plan}")
    # storage faults live behind the readers; in-flight faults wrap the
    # transport (sim/local) or run server-side (tcp)
    serve_store = (
        with_faulty_backend(store, fault_plan)
        if fault_plan is not None and args.fault_missing > 0
        else store
    )
    inflight_faults = fault_plan is not None and bool(
        args.fault_drop or args.fault_stall or args.fault_corrupt
        or args.fault_truncate
    )

    tcp_server = None
    transport = None  # sim: a SimTransport is built per request below
    if args.transport == "local":
        transport = LocalTransport(serve_store)
        if inflight_faults:
            transport = FaultyTransport(transport, fault_plan)
    elif args.transport == "tcp":
        tcp_server = TcpStoreServer(
            serve_store, pace_gbps=args.tcp_pace_gbps,
            fault_plan=fault_plan if inflight_faults else None,
        )
        transport = TcpTransport.for_server(
            tcp_server,
            # content-addressed protocol: the client sends hash keys when
            # the store has them, and the server reads by (hash, level)
            hash_lookup=getattr(serve_store, "try_hash", None),
        )
        print(f"[serve] tcp store server on {tcp_server.address} "
              f"paced at {args.tcp_pace_gbps} Gbps")

    def mk_transport(net):
        """Per-request fetch path with the fault plan applied."""
        if transport is not None:
            return transport
        if serve_store is store and not inflight_faults:
            return None  # default: SessionTask builds a clean SimTransport
        t = SimTransport(serve_store, net)
        return FaultyTransport(t, fault_plan) if inflight_faults else t

    retry_policy = None
    if args.retry >= 1:
        retry_policy = RetryPolicy(
            max_attempts=args.retry,
            backoff_s=args.retry_backoff,
            timeout_s=None if args.transport != "sim" else args.retry_timeout,
            wall_timeout_s=args.retry_timeout if args.transport != "sim" else None,
            degrade=not args.no_degrade,
        )
        print(f"[serve] retry policy armed: {retry_policy}")

    recompute_s = lambda t, p: 0.02 * t / 64  # noqa: E731
    session = ServeSession(
        streamer,
        engine,
        slo_s=args.slo_ms / 1e3,
        recompute_s=recompute_s,
        decode_bytes_per_s=300e6,
        allow_text=(cfg.family != "vlm"),
        fixed_level=args.fixed_level,
        max_run_tokens=args.max_run_tokens,
        hedge_after_s=args.hedge_after,
        transport=transport,
        retry_policy=retry_policy,
        resume_fetch=not args.no_resume,
        replan_factor=args.replan_factor,
    )

    def close_server():
        counters = getattr(serve_store, "tier_counters", None)
        if callable(counters):
            c = counters()
            print(
                f"[serve] tiered store: hot_hits={c['hot_hits']} "
                f"cold_hits={c['cold_hits']} misses={c['misses']} "
                f"demotions={c['demotions']} evictions={c['evictions']} "
                f"dedup_chunks={c['dedup_chunks']} "
                f"hot={c['hot_used_bytes']/1e3:.1f}/"
                f"{min(c['hot_capacity_bytes'], 1 << 40)/1e3:.1f} KB "
                f"unique={c['unique_bytes']/1e3:.1f} KB"
            )
        if tcp_server is None:
            return
        tcp_server.close()
        if fault_plan is not None:
            print(
                f"[serve] tcp server: conns={tcp_server.n_connections} "
                f"dropped={tcp_server.n_dropped_connections} "
                f"malformed={tcp_server.n_malformed} "
                f"injected={tcp_server.n_injected_faults}"
            )
        stats = getattr(transport, "tier_stats", None)
        if callable(stats):
            s = stats()
            print(
                f"[serve] tcp client: connects={s.get('n_connects', 0)} "
                f"reconnects={s.get('n_reconnects', 0)} "
                f"pool_reuses={s.get('n_pool_reuses', 0)}"
            )

    names = {TEXT: "TEXT"}

    def describe(r, res, extra=""):
        fault = ""
        if retry_policy is not None or fault_plan is not None:
            fault = (
                f" retries={res.n_retries} degrades={res.n_degrades} "
                f"faults={res.fault_counts}"
            )
            if retry_policy is not None:
                fault += (
                    f" salvaged={res.salvaged_bytes/1e3:.1f}KB "
                    f"resumes={res.n_resumes} "
                    f"replans={res.n_mid_chunk_replans}"
                )
        if res.failed:
            print(
                f"[req {r}] FAILED ({res.failure}) "
                f"configs={[names.get(c, f'L{c}') for c in res.configs]}"
                + fault + extra
            )
            return
        first = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        gen = engine.generate_with_kv(res.caches, first, args.gen)
        hedge = (
            f" hedged={res.n_hedged} dup={res.duplicate_bytes/1e3:.1f}KB"
            if args.hedge_after is not None else ""
        )
        print(
            f"[req {r}] configs={[names.get(c, f'L{c}') for c in res.configs]} "
            f"ttft={res.ttft_s*1e3:.1f} ms ok={not res.slo_violated} "
            f"runs={res.n_runs} "
            f"tokens={gen[0].tolist()}" + hedge + fault + extra
        )

    def check_sim(res, trace, prior):
        if not args.check_sim:
            return ""
        plan = streamer.stream(
            "ctx", NetworkModel(trace, rtt_s=0.002), slo_s=args.slo_ms / 1e3,
            decode_bytes_per_s=300e6, recompute_s=recompute_s,
            prior_throughput_gbps=prior, allow_text=(cfg.family != "vlm"),
            fixed_level=args.fixed_level, hedge_after_s=args.hedge_after,
        )
        return f" sim_match={res.configs == plan.result.configs}"

    if args.arrivals is not None:
        from repro.serving.generation import GenerationSpec
        from repro.serving.scheduler import (
            ContinuousScheduler,
            PreemptionPolicy,
            SessionRequest,
        )

        arrivals = _parse_arrivals(args.arrivals, args.requests, args.arrival_seed)
        traces = [
            BandwidthTrace.sampled(rng, 6, 0.05, 0.05, 2.0)
            for _ in range(args.requests)
        ]
        gen_spec = None
        if args.generate:
            # first decode input = the context prefill's TTFT token
            first_tok = int(jnp.argmax(logits[0, -1]))
            gen_spec = GenerationSpec(
                n_tokens=args.generate,
                first_token=first_tok,
                gen_slo_s=args.gen_slo,
                sample_seed=args.sample_seed,
            )
        scheduler = ContinuousScheduler(
            engine,
            rows=args.rows if args.rows is not None else args.concurrency,
            preemption=(
                PreemptionPolicy(margin_s=args.preempt_margin)
                if args.preempt else None
            ),
            gen_step_s=args.gen_step_ms / 1e3,
        )
        nets = [NetworkModel(tr, rtt_s=0.002) for tr in traces]
        out = scheduler.run([
            SessionRequest(
                session, "ctx", tokens, net,
                prior_throughput_gbps=float(tr.gbps[0]), start_t=arr,
                transport=mk_transport(net),
                generation=gen_spec,
            )
            for tr, net, arr in zip(traces, nets, arrivals)
        ])
        for r, (res, tl) in enumerate(zip(out.sessions, out.timeline)):
            extra = (
                f" arrival={tl.arrival_t*1e3:.0f}ms wait={tl.queue_wait_s*1e3:.0f}ms"
                + (f" preempted={tl.n_preemptions}x" if tl.n_preemptions else "")
            )
            if tl.n_tokens_out:
                # host clock: run start to the first token's logits on the
                # host, and the mean gap between the token syncs
                wall = tl.token_wall
                gap = (wall[-1] - wall[0]) / max(len(wall) - 1, 1)
                extra += (
                    f" gen={tl.n_tokens_out}tok"
                    f" tpot_mean={tl.mean_tpot_s*1e3:.2f}ms"
                    f" wall_ttft={(wall[0] - tl.start_wall)*1e3:.1f}ms"
                    f" wall_gap={gap*1e3:.2f}ms"
                )
            describe(r, res, extra)
        ttfts = sorted(s.ttft_s for s in out.sessions)
        p = lambda q: ttfts[min(int(q * len(ttfts)), len(ttfts) - 1)]  # noqa: E731
        resume = ""
        if retry_policy is not None:
            resume = (
                f" salvaged={sum(s.salvaged_bytes for s in out.sessions)/1e3:.1f}KB"
                f" fetch_resumes={sum(s.n_resumes for s in out.sessions)}"
                f" replans={sum(s.n_mid_chunk_replans for s in out.sessions)}"
            )
        print(
            f"[open-loop rows={out.n_rows}] ttft p50={p(0.5)*1e3:.1f} ms "
            f"p95={p(0.95)*1e3:.1f} ms preemptions={out.n_preemptions} "
            f"resumes={out.n_resumes} rounds={out.n_rounds} "
            f"decode_batches={out.n_decode_batches} "
            f"peak_rows={max(n for _, n in out.occupancy)} "
            f"failed={out.n_failed}" + resume
        )
        if out.n_gen_tokens:
            tpots = sorted(
                d for tl in out.timeline for d in tl.tpot_s
            )
            pq = lambda q: tpots[min(int(q * len(tpots)), len(tpots) - 1)]  # noqa: E731
            agg = (
                out.n_gen_tokens / out.wall_gen_s if out.wall_gen_s > 0
                else float("nan")
            )
            peak_gen = max((n for _, n in out.gen_occupancy), default=0)
            print(
                f"[generation tokens={out.n_gen_tokens}] "
                f"tpot mean={sum(tpots)/len(tpots)*1e3:.2f} ms "
                f"p95={pq(0.95)*1e3:.2f} ms "
                f"agg {agg:.1f} tok/s steps={out.n_gen_steps} "
                f"peak_gen_rows={peak_gen}"
            )
        close_server()
        return

    if args.concurrency == 1:
        for r in range(args.requests):
            trace = BandwidthTrace.sampled(rng, 6, 0.05, 0.05, 2.0)
            prior = float(trace.gbps[0])
            net = NetworkModel(trace, rtt_s=0.002)
            res = session.run(
                "ctx",
                tokens,
                net,
                prior_throughput_gbps=prior,
                transport=mk_transport(net),
            )
            describe(r, res, check_sim(res, trace, prior))
        close_server()
        return

    from repro.serving.scheduler import ConcurrentScheduler, SessionRequest

    if args.check_sim:
        # the offline simulator has no contention model, so comparing its
        # decisions is only meaningful with contention charging disabled
        # (factor 1 at any N); without --check-sim, waves use the measured
        # contention model and decisions legitimately diverge from the
        # uncontended simulator under load
        from repro.streaming.pipeline import ContentionModel

        scheduler = ConcurrentScheduler(
            engine, contention=ContentionModel({1: 1.0, 2: 1.0})
        )
    else:
        scheduler = ConcurrentScheduler(engine)
    served = 0
    while served < args.requests:
        wave = min(args.concurrency, args.requests - served)
        traces = [BandwidthTrace.sampled(rng, 6, 0.05, 0.05, 2.0) for _ in range(wave)]
        nets = [NetworkModel(tr, rtt_s=0.002) for tr in traces]
        out = scheduler.run([
            SessionRequest(
                session, "ctx", tokens, net,
                prior_throughput_gbps=float(tr.gbps[0]),
                transport=mk_transport(net),
            )
            for tr, net in zip(traces, nets)
        ])
        for i, res in enumerate(out.sessions):
            describe(served + i, res, check_sim(res, traces[i], float(traces[i].gbps[0])))
        resume = ""
        if retry_policy is not None:
            resume = (
                f" salvaged={sum(s.salvaged_bytes for s in out.sessions)/1e3:.1f}KB"
                f" fetch_resumes={sum(s.n_resumes for s in out.sessions)}"
                f" replans={sum(s.n_mid_chunk_replans for s in out.sessions)}"
            )
        print(
            f"[wave of {wave}] decode_batches={out.n_decode_batches} "
            f"text_batches={out.n_text_batches} runs={out.n_runs} "
            f"wall_total={out.wall_total_s*1e3:.1f} ms failed={out.n_failed}"
            + resume
        )
        served += wave
    close_server()


if __name__ == "__main__":
    main()
