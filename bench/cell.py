"""One benchmark cell: set-up, the measured window, and the check.

A cell is a configuration (``bench/configs/<config>.json``) under a traffic
mix (``bench/traffic/<mix>.json``), sized by its cell file
(``bench/cells/<workload>.json``: clients, documents, limits).  Everything
architecture-specific comes from the reference module that the
configuration's ``reference`` key names (``bench/reference/__init__.py``):
the weights, the program's architecture fields it implies, and the check.
The program's architecture is its ``registry`` entry with those fields put
in, so that a cut to the chip's share (layers kept, experts held, a
vocabulary slice) is written once, in the configuration's own keys listed
in ``reduced``, and the program and the reference both read it.

Set-up makes the weights from the seed on the device, prefills the
documents, profiles the codec on a calibration sample of the first document,
stores every level, and runs a warm-up wave plus one decode of every run
shape the window can meet.  The window is a closed loop of waves: a wave
hands one request per client (one per cache row) to the program's
``ContinuousScheduler.run``, every request due at the wave's start; each
request fetches its document's chunks, decodes them (rANS, then the token
kernels), inserts them, and generates its output greedily.  Waves run back
to back until the window's seconds have passed.

Times come from the host clock.  A thin wrapper on the engine's
``decode_step_rows`` stamps the host time once each step's logits are on
the host, which is where the scheduler reads them: a request's first stamp
is its first token, and its later stamps its later tokens.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import time
from typing import Dict, List, Optional

import numpy as np

from bench import reference
from bench import traffic as traffic_mod

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def workload_entry(name: str) -> dict:
    """The cell's entry in ``BENCHMARK.json`` at the checkout's root."""
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile (0 < q < 100) by linear interpolation."""
    return float(np.percentile(np.asarray(values, np.float64), q))


@dataclasses.dataclass
class Record:
    """What a run counted, for the per-layer readers in ``bench/metrics``."""

    cfg: dict
    codec: dict
    device_kind: str
    chunk_tokens: int
    n_requests: int = 0
    n_rounds: int = 0
    n_gen_steps: int = 0
    n_gen_tokens: int = 0
    context_tokens: int = 0
    wire_bytes: float = 0.0
    chunks_lossless: int = 0
    chunks_lossy: int = 0
    step_lengths: List[List[int]] = dataclasses.field(default_factory=list)
    compiles_in_window: int = 0
    trace: Optional[dict] = None


class Probe:
    """Instruments the engine instance and the codec entry the scheduler
    calls: host stamps of decode steps, counts of decoded chunks, and (when
    tracing) host spans named ``bench.<call>``."""

    def __init__(self, engine, codec_module, annotate: bool):
        import jax

        self.steps: List[tuple] = []  # (host time, active rows)
        self.decode_calls: List[tuple] = []  # (n_lossless, n_lossy)
        self.annotate = annotate
        span = jax.profiler.TraceAnnotation
        orig_step = engine.decode_step_rows
        orig_insert = engine.insert_runs
        orig_decode = codec_module.decode_chunk_runs
        peek = codec_module.peek_chunk_header
        null = _Null()

        def decode_step_rows(tokens, caches, active):
            with span("bench.decode_step_rows") if self.annotate else null:
                logits, caches = orig_step(tokens, caches, active)
                logits.block_until_ready()
            self.steps.append((time.perf_counter(), np.asarray(active)))
            return logits, caches

        def insert_runs(*a, **k):
            with span("bench.insert_runs") if self.annotate else null:
                return orig_insert(*a, **k)

        def decode_chunk_runs(runs, *a, **k):
            levels = [int(peek(b)["level"]) for run in runs for b in run]
            n0 = sum(1 for lvl in levels if lvl == 0)
            self.decode_calls.append((n0, len(levels) - n0))
            with span("bench.decode_chunk_runs") if self.annotate else null:
                return orig_decode(runs, *a, **k)

        engine.decode_step_rows = decode_step_rows
        engine.insert_runs = insert_runs
        codec_module.decode_chunk_runs = decode_chunk_runs
        self.orig_decode = orig_decode
        self._codec = codec_module

    def close(self) -> None:
        """Give the codec module its own entry back."""
        self._codec.decode_chunk_runs = self.orig_decode


class _Events:
    """JAX's compile-path events and the interpreter's garbage collections
    while the window runs, so that the log can say what a slow wave waited
    on.  A program that the window builds, compiled or loaded from the
    persistent cache, counts in ``built``."""

    BUILT = ("/jax/core/compile/backend_compile_duration",
             "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.jax: List[tuple] = []  # (event, function, seconds)
        self.gc: List[tuple] = []  # (generation, seconds)
        self.on = True
        self._gc_t0 = None
        jax.monitoring.register_event_duration_secs_listener(self._on_jax)
        gc.callbacks.append(self._on_gc)

    def _on_jax(self, event: str, duration: float, **kw):
        if self.on:
            self.jax.append((event, kw.get("fun_name", ""), float(duration)))

    def _on_gc(self, phase: str, info: dict):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self.on and self._gc_t0 is not None:
            self.gc.append((info["generation"], time.perf_counter() - self._gc_t0))

    def close(self) -> None:
        self.on = False
        gc.callbacks.remove(self._on_gc)

    def built(self) -> int:
        return sum(1 for e, _, _ in self.jax if e in self.BUILT)

    def traced(self) -> int:
        return sum(1 for e, _, _ in self.jax if e.endswith("jaxpr_trace_duration"))

    def since(self, n_jax: int, n_gc: int) -> str:
        """The events after the first ``n_jax`` and ``n_gc``, summed by kind."""
        by = {}
        for e, fun, d in self.jax[n_jax:]:
            k = f"{e.rsplit('/', 1)[-1]}:{fun}"
            c, t = by.get(k, (0, 0.0))
            by[k] = (c + 1, t + d)
        top = sorted(by.items(), key=lambda kv: -kv[1][1])[:4]
        jx = ", ".join(f"{k} x{c} {t:.3f} s" for k, (c, t) in top) or "none"
        g = self.gc[n_gc:]
        return (f"jax: {jx}; gc: {len(g)} collections {sum(d for _, d in g):.3f} s, "
                f"longest {max((d for _, d in g), default=0.0):.3f} s")


def _memory(device) -> str:
    m = device.memory_stats() or {}
    return ", ".join(f"{k} {m[k] / 1e9:.3f} GB" for k in
                     ("bytes_in_use", "peak_bytes_in_use", "largest_free_block_bytes")
                     if k in m) or "no memory stats"


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class Cell:
    """``overrides`` replaces entries of the configuration, traffic or cell
    files (``{"config": {...}, "traffic": {...}, "cell": {...}}``): the
    tests drive a run at a size a CPU can hold this way.

    With ``control`` the program serves with its weights rounded to the
    dtype of the cell's ``control``: a lower precision that the check has to
    fail.  The benchmark's own runs never set it."""

    def __init__(self, workload: str, seed: int, log, overrides: Optional[dict] = None,
                 control: bool = False):
        entry = workload_entry(workload)
        over = overrides or {}
        self.name = workload
        self.seed = int(seed)
        self.log = log
        self.config_file = os.path.join("bench", "configs", f"{entry['config']}.json")
        self.cfg = dict(load_json("configs", f"{entry['config']}.json"), **over.get("config", {}))
        self.ref = reference.load(self.cfg["reference"], self.config_file)
        self.mix = dict(traffic_mod.load(entry["traffic"]), **over.get("traffic", {}))
        self.cell = dict(load_json("cells", f"{workload}.json"), **over.get("cell", {}))
        self.chips = int(entry["chips"])
        self.codec = dict(self.cfg["codec"], chunk_tokens=self.mix["chunk_tokens"])
        self.rng = np.random.default_rng(self.seed)
        self.control = bool(control)
        self.phases: Dict[str, float] = {}

    # -- set-up -------------------------------------------------------------

    def _phase(self, name: str, t0: float) -> float:
        t = time.perf_counter()
        self.phases[name] = t - t0
        self.log(f"[setup] {name}: {t - t0:.3f} s")
        return t

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.configs import registry
        from repro.core import codec as kvcodec
        from repro.serving.engine import Engine
        from repro.serving.kv_layout import caches_to_codec_kv
        from repro.streaming import CacheGenStreamer, KVStore

        cfg, cell, mix, ref = self.cfg, self.cell, self.mix, self.ref
        t = time.perf_counter()
        self.arch = dataclasses.replace(registry.get(cfg["registry"]), **ref.program_fields(cfg))
        init = jax.jit(lambda key: ref.init_params(cfg, key))
        self.params = jax.block_until_ready(init(ref.make_key(self.seed)))
        served = self.params
        if self.control:
            low = jnp.dtype(cell["control"]["weights"])
            served = jax.tree_util.tree_map(lambda w: w.astype(low).astype(w.dtype),
                                            self.params)
        self.engine = Engine(self.arch, served, cache_capacity=cfg["capacity"])
        t = self._phase("weights", t)

        ct = mix["chunk_tokens"]
        self.n_docs = int(cell["documents"])
        self.doc_chunks = traffic_mod.doc_chunks(mix, self.n_docs)
        self.docs, self.first_tokens, kvs = [], [], []
        for n in self.doc_chunks:
            toks = traffic_mod.document_tokens(
                self.rng, n * ct, cfg["vocab_size"], mix["token_zipf_a"])
            logits, caches = self.engine.calculate_kv({"tokens": jnp.asarray(toks[None])})
            kvs.append(caches_to_codec_kv(caches, 0, n * ct))
            self.first_tokens.append(int(jnp.argmax(logits[0, -1])))
            self.docs.append(toks)
            del caches, logits
        t = self._phase("prefill", t)

        calib = mix["calibration_tokens"]
        self.calib_tokens = self.docs[0][:calib]
        c = self.codec
        codec_cfg = kvcodec.CodecConfig(
            group_size=c["group_size"], layer_group_bins=tuple(c["layer_group_bins"]),
            level_mults=tuple(c["level_mults"]), delta_qmax=c["delta_qmax"],
            precision=c["precision"],
        )
        tables = kvcodec.profile([kvs[0][:, :, :calib]], codec_cfg)
        t = self._phase("profile", t)

        self.store = KVStore(tables)
        for i, (toks, kv) in enumerate(zip(self.docs, kvs)):
            self.store.store_kv(f"doc{i}", kv, chunk_tokens=ct, tokens=toks.tolist())
        del kvs
        self.streamer = CacheGenStreamer(self.store, self.arch)
        t = self._phase("encode", t)

        self.session = self._session()
        self.probe = Probe(self.engine, kvcodec, annotate=False)
        self._run_wave(self._new_wave(np.random.default_rng([self.seed, 1])))
        calls = self.probe.decode_calls
        levels = ([0] if any(n0 for n0, _ in calls) else []) + (
            [lvl for lvl in self._levels() if lvl] if any(n1 for _, n1 in calls) else [])
        self._warm_decode_shapes({n0 + n1 for n0, n1 in calls}, levels)
        t = self._phase("warm-up", t)

    def _warm_decode_shapes(self, sizes, levels) -> None:
        """Decode and insert every run size the warm-up wave met, once per
        distinct padded stream width among the stored chunks at the levels
        it served, so that no run of the window compiles."""
        import jax
        import jax.numpy as jnp

        from repro.core import bitstream

        ct = self.mix["chunk_tokens"]
        by_width = {}
        for i, n in enumerate(self.doc_chunks):
            for ci in range(n):
                for lvl in levels:
                    blob = self.store.get_kv(f"doc{i}", ci, lvl)
                    _, arrays = bitstream.unpack(blob)
                    key = tuple(
                        -(-bitstream.unpack_stream(arrays, p)[0].shape[1] // 64)
                        for p in ("a", "d")
                    ) + (lvl == 0,)
                    by_width.setdefault(key, blob)
        rows = int(self.cell["clients"])
        caches = self.engine.empty_caches(rows)
        # the served path decodes into the cache's own dtype
        dtype = next(x.dtype for x in jax.tree_util.tree_leaves(caches)
                     if jnp.issubdtype(x.dtype, jnp.floating))
        for n in sorted(sizes):
            for blob in by_width.values():
                kv, spans = self.probe.orig_decode(
                    [[blob]] * n, self.store.tables,
                    out_dtype=dtype, run_tokens=[ct] * n,
                )
                caches = self.engine.insert_runs(
                    caches, kv, rows=list(range(n)), starts=[0] * n,
                    run_tokens=[m for _, m in spans],
                )
        jax.block_until_ready(caches)
        self.probe.decode_calls.clear()
        self.probe.steps.clear()

    def _levels(self) -> List[int]:
        lv = self.mix["levels"]
        return list(range(len(self.codec["level_mults"]) + 1)) if lv == "adaptive" else [int(lv)]

    # -- waves --------------------------------------------------------------

    def _new_wave(self, rng) -> List[dict]:
        return traffic_mod.wave(self.mix, rng, self.n_docs, int(self.cell["clients"]))

    def _session(self):
        from repro.serving.session import ServeSession

        mix = self.mix
        lv = mix["levels"]
        return ServeSession(
            self.streamer, self.engine, slo_s=float(mix["slo_s"]),
            recompute_s=lambda tokens, prefix: float("inf"),
            allow_text=bool(mix["allow_text"]),
            fixed_level=None if lv == "adaptive" else int(lv),
            max_run_tokens=int(mix["max_run_tokens"]),
        )

    def _run_wave(self, wave: List[dict]):
        """Serve one wave; returns (wave start, scheduler result, stamps of
        the wave's decode steps)."""
        from repro.serving.generation import GenerationSpec
        from repro.serving.scheduler import ContinuousScheduler, SessionRequest
        from repro.streaming import BandwidthTrace, NetworkModel, SimTransport

        session = self.session
        reqs = []
        for w in wave:
            trace = BandwidthTrace(np.asarray(w["times"]), np.asarray(w["gbps"]))
            net = NetworkModel(trace, rtt_s=float(self.mix["link"]["rtt_s"]))
            doc = self.docs[w["doc"]]
            reqs.append(SessionRequest(
                session, f"doc{w['doc']}", doc[None], net,
                prior_throughput_gbps=float(trace.gbps[0]),
                transport=SimTransport(self.store, net,
                                       time_scale=float(self.mix["link"]["pace"])),
                generation=GenerationSpec(w["n_out"], self.first_tokens[w["doc"]]),
            ))
        sched = ContinuousScheduler(self.engine, rows=int(self.cell["clients"]))
        first = len(self.probe.steps)
        t0 = time.perf_counter()
        out = sched.run(reqs)
        return t0, out, self.probe.steps[first:]

    # -- window -------------------------------------------------------------

    def window(self, seconds: float, trace_dir: Optional[str]) -> dict:
        import jax

        device = jax.devices()[0]
        rec = Record(cfg=self.cfg, codec=self.codec, device_kind=device.device_kind,
                     chunk_tokens=self.mix["chunk_tokens"])
        self.probe.annotate = trace_dir is not None
        self.probe.decode_calls.clear()
        ttft, gaps, served = [], [], []
        attempted = failed = 0
        wave_rng = np.random.default_rng([self.seed, 2])
        if trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # host spans only: no per-call tracing
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        events = _Events()
        w0 = time.perf_counter()
        while time.perf_counter() - w0 < seconds:
            wave = self._new_wave(wave_rng)
            n_jax, n_gc = len(events.jax), len(events.gc)
            with (jax.profiler.TraceAnnotation("bench.wave") if trace_dir else _Null()):
                t0, out, steps = self._run_wave(wave)
            attempted += len(wave)
            self._account(rec, wave, out, steps, t0, ttft, gaps, served)
            stamps = [t0] + [t for t, _ in steps]
            gap, at = max((b - a, i) for i, (a, b) in enumerate(zip(stamps, stamps[1:])))
            self.log(f"[wave] {time.perf_counter() - t0:.3f} s, {len(steps)} steps, "
                     f"longest wait {gap:.3f} s before step {at}; "
                     f"{events.since(n_jax, n_gc)}; {_memory(device)}")
            failed += out.n_failed
            del out
        w1 = time.perf_counter()
        events.close()
        rec.compiles_in_window = events.built()
        retraced = events.traced()
        if trace_dir is not None:
            jax.profiler.stop_trace()
        stats = device.memory_stats() or {}
        for n0, n1 in self.probe.decode_calls:
            rec.chunks_lossless += n0
            rec.chunks_lossy += n1
        self.record = rec
        self.served = served
        ok = attempted - failed
        self.log(
            f"[window] {w1 - w0:.3f} s, {attempted} requests, {failed} failed, "
            f"{rec.n_gen_tokens} tokens, {rec.compiles_in_window} programs built, "
            f"{retraced} traced, "
            f"lossless chunks {rec.chunks_lossless}, lossy chunks {rec.chunks_lossy}"
        )
        return dict(
            attempted=attempted, failed=failed, window_s=w1 - w0,
            ttft_p95_ms=percentile(ttft, 95) * 1e3 if ttft else None,
            tpot_p95_ms=percentile(gaps, 95) * 1e3 if gaps else None,
            requests_per_s=ok / (w1 - w0),
            ttft_p50_ms=percentile(ttft, 50) * 1e3 if ttft else None,
            memory_peak_bytes=int(stats.get("peak_bytes_in_use", 0)),
        )

    def _account(self, rec, wave, out, steps, t0, ttft, gaps, served):
        ct = self.mix["chunk_tokens"]
        rec.n_rounds += out.n_rounds
        rec.n_gen_steps += out.n_gen_steps
        rec.n_gen_tokens += out.n_gen_tokens
        lengths_by_step = [[] for _ in steps]
        for w, s, tl in zip(wave, out.sessions, out.timeline):
            rec.n_requests += 1
            if s.status != "ok":
                continue
            (row,) = set(tl.rows_used)
            mine = [i for i, (_, act) in enumerate(steps) if act[row]]
            stamps = [steps[i][0] for i in mine]
            if len(stamps) != len(tl.tokens_out):
                raise RuntimeError(
                    f"request on row {row}: {len(stamps)} decode steps for "
                    f"{len(tl.tokens_out)} tokens")
            T = len(self.docs[w["doc"]])
            for k, i in enumerate(mine):
                lengths_by_step[i].append(T + k)
            ttft.append(stamps[0] - t0)
            gaps.extend(np.diff(stamps).tolist())
            metas = self.store.meta(f"doc{w['doc']}")
            rec.wire_bytes += sum(metas[i].sizes[c] for i, c in enumerate(s.configs))
            rec.context_tokens += T
            served.append(dict(
                doc=w["doc"], levels=[int(c) for c in s.configs],
                tokens=[self.first_tokens[w["doc"]]] + [int(x) for x in tl.tokens_out],
                chunks=T // ct,
            ))
        rec.step_lengths.extend(lengths_by_step)

    # -- check --------------------------------------------------------------

    def free_program(self) -> None:
        """Drop the program's state; the weights stay for the reference."""
        import jax

        if hasattr(self, "probe"):
            self.probe.close()
        for name in ("engine", "store", "streamer", "probe", "session"):
            if hasattr(self, name):
                delattr(self, name)
        gc.collect()
        jax.clear_caches()

    def sample(self) -> List[dict]:
        """The requests the reference checks: the longest finished request
        and others drawn from the seed, until some hundreds of served tokens
        are covered."""
        served = self.served
        if not served:
            return []
        rng = np.random.default_rng([self.seed, 3])
        longest = max(range(len(served)),
                      key=lambda i: (served[i]["chunks"], len(served[i]["tokens"])))
        picked = [longest]
        for i in rng.permutation(len(served)):
            if sum(len(served[j]["tokens"]) for j in picked) >= self.cell["check_tokens"]:
                break
            if i != longest:
                picked.append(int(i))
        return [dict(served[i], doc=self.docs[served[i]["doc"]]) for i in picked]

    def check(self, extra_sides: Optional[dict] = None) -> dict:
        """The sampled served tokens against the plain reference.

        Two numbers are compared: ``gap``, the widest gap of a served
        token's logit below the reference's best, and ``gap_share``, the
        served tokens' mean gap over the mean gap of the tokens that the
        cell's ``control`` (the reference with its weights and numerics one
        precision below the served one) puts first on the same prompts and
        tokens.  Both means scale with how often the seed's random model
        meets a near-tie of its top two logits; their ratio does not, so one
        limit holds for every seed.  The control's own numbers are
        ``control_gap`` and ``control_share`` (1).  ``extra_sides`` adds
        further ``name: (weights dtype, numerics)`` runs of the reference,
        and each one's ``<name>_share``, its mean gap over the control's."""
        sample = self.sample()
        if not sample:
            inf = float("inf")
            return dict(gap=inf, gap_mean=inf, flip_share=inf, gap_share=inf,
                        control_gap=inf, control_gap_mean=inf, n_requests=0, n_tokens=0)
        c = self.cell["control"]
        sides = dict(control=(c["weights"], (c["operands"], c["stored"])), **(extra_sides or {}))
        max_tokens = int(self.mix["output_tokens"][1]) + 1
        gaps = self.ref.served_gaps(
            self.params, self.cfg, sample, self.codec, self.calib_tokens,
            max_tokens=max_tokens, sides=sides,
        )
        low = gaps["control_gap_mean"]
        for name in ("", "control", *(extra_sides or {})):
            mean = gaps[f"{name}_gap_mean" if name else "gap_mean"]
            gaps[f"{name}_share" if name else "gap_share"] = (
                mean / low if low > 0 else (0.0 if mean == 0 else float("inf")))
        gaps["n_requests"] = len(sample)
        gaps["n_tokens"] = sum(len(r["tokens"]) for r in sample)
        return gaps

