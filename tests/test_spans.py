"""Host spans of the served path and the counters returned beside them.

* spans — a ``ContinuousScheduler`` run of the tiny config under
  ``jax.profiler.trace`` on the CPU, read back from the ``/host:CPU`` plane
  with ``ProfileData``: every span the program names appears, they nest as
  designed (``sched.run`` > ``sched.round`` > ``stream.step`` >
  ``stream.fetch_wait``; ``sched.gen_step`` > ``sched.logits_sync``), the
  ``stream.*`` spans carry the request's label, the span counts equal
  the scheduler's own round and step counts, and each ``sched.logits_sync``
  of the all-greedy run carries ``pick=device``;
* counters — on the benchmark's tiny cell, the chunks decoded at each level
  (read from the sessions' realized ``configs``) equal what the benchmark's
  probe counts from outside, and each request's ``token_wall`` has one
  entry per token, each at or after the probe's host stamp of the same
  step and within 50 ms of it.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import codec as kvcodec
from repro.serving.generation import GenerationSpec
from repro.serving.scheduler import ContinuousScheduler, SessionRequest
from repro.serving.session import ServeSession
from repro.streaming import CacheGenStreamer, KVStore
from repro.streaming.adaptation import TEXT
from repro.streaming.network import BandwidthTrace, NetworkModel

T_CTX = 60
CHUNK = 20  # 3 chunks
LAYERS = ("sched", "stream", "codec", "engine")
SPANS = (
    "sched.run", "sched.admit", "sched.round", "sched.complete",
    "sched.gen_step", "sched.logits_sync", "stream.step", "stream.decide",
    "stream.fetch_wait", "codec.parse", "codec.dispatch",
    "engine.insert_runs", "engine.recompute",
)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    from repro.configs import registry
    from repro.models import build
    from repro.serving.engine import Engine
    from repro.serving.kv_layout import caches_to_codec_kv

    rng = np.random.default_rng(0)
    cfg = registry.get("smollm-360m").tiny()
    params = build(cfg).init_params(jax.random.PRNGKey(0))
    eng = Engine(cfg, params, cache_capacity=T_CTX + 16)
    tokens = rng.integers(0, cfg.vocab_size, size=(1, T_CTX)).astype(np.int32)
    logits, caches = eng.calculate_kv({"tokens": jnp.asarray(tokens)})
    kv = caches_to_codec_kv(caches, 0, T_CTX)
    store = KVStore(kvcodec.profile([kv], kvcodec.CodecConfig(precision=10)))
    store.store_kv("ctx", kv, chunk_tokens=CHUNK)
    streamer = CacheGenStreamer(store, cfg)
    first = int(jnp.argmax(logits[0, -1]))
    trace = BandwidthTrace.constant(0.01)

    def request(n_out, **kw):
        sess = ServeSession(streamer, eng, slo_s=1.0, decode_bytes_per_s=1e9,
                            max_run_tokens=2 * CHUNK, **kw)
        return SessionRequest(sess, "ctx", tokens, NetworkModel(trace),
                              prior_throughput_gbps=0.01,
                              generation=GenerationSpec(n_out, first))

    # two bitstream loads and one all-TEXT load on two rows: the third
    # request waits for a row, which is reset before it is reused
    reqs = [
        request(3, fixed_level=0, recompute_s=lambda t, p: 100.0),
        request(2, fixed_level=1, recompute_s=lambda t, p: 100.0),
        request(2, recompute_s=lambda t, p: 1e-6),
    ]
    sched = ContinuousScheduler(eng, rows=2)
    sched.run(reqs)  # compile outside the trace
    out_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(out_dir):
        out = sched.run(reqs)
    (path,) = glob.glob(os.path.join(out_dir, "plugins", "profile", "*", "*.xplane.pb"))
    (host,) = [p for p in ProfileData.from_file(path).planes if p.name == "/host:CPU"]
    events = []
    for line in host.lines:
        evs = [dict(start=ev.start_ns, end=ev.end_ns, name=ev.name, stats=dict(ev.stats))
               for ev in line.events if ev.name.split(".")[0] in LAYERS]
        for ev in evs:
            inside = [p for p in evs if p is not ev and p["start"] <= ev["start"]
                      and ev["end"] <= p["end"]]
            ev["parent"] = min(inside, key=lambda p: p["end"] - p["start"], default=None)
        events.extend(evs)
    return out, events


def _named(events, name):
    return [ev for ev in events if ev["name"] == name]


def _ancestors(ev):
    out = []
    while ev["parent"] is not None:
        ev = ev["parent"]
        out.append(ev["name"])
    return out


def test_every_span_appears(traced):
    out, events = traced
    assert {ev["name"] for ev in events} == set(SPANS)
    assert any(c == TEXT for s in out.sessions for c in s.configs)


def test_spans_nest_as_designed(traced):
    _, events = traced
    (run,) = _named(events, "sched.run")
    assert run["parent"] is None
    parent = {
        "sched.admit": "sched.run", "sched.round": "sched.run",
        "sched.gen_step": "sched.run", "sched.complete": "sched.round",
        "stream.step": "sched.round", "stream.decide": "stream.step",
        "stream.fetch_wait": "stream.step", "sched.logits_sync": "sched.gen_step",
    }
    for name, want in parent.items():
        for ev in _named(events, name):
            assert ev["parent"]["name"] == want, (name, _ancestors(ev))
    chain = ["stream.step", "sched.round", "sched.run"]
    for ev in _named(events, "stream.fetch_wait"):
        assert _ancestors(ev) == chain
    # the load path's host work all runs inside a round
    for name in ("codec.parse", "codec.dispatch", "engine.insert_runs", "engine.recompute"):
        for ev in _named(events, name):
            assert "sched.round" in _ancestors(ev), (name, _ancestors(ev))


def test_stream_spans_carry_the_request(traced):
    out, events = traced
    labels = {f"req{i}:ctx" for i in range(len(out.sessions))}
    for ev in events:
        if ev["name"].startswith("stream."):
            assert ev["stats"]["req"] in labels
            assert ev["stats"]["chunk"] in range(T_CTX // CHUNK)
    levels = {ev["stats"]["level"] for ev in _named(events, "stream.fetch_wait")}
    assert levels == {0, 1}


def test_span_counts_equal_scheduler_counts(traced):
    out, events = traced
    assert len(_named(events, "sched.gen_step")) == out.n_gen_steps
    assert len(_named(events, "sched.logits_sync")) == out.n_gen_steps
    assert len(_named(events, "sched.round")) == out.n_rounds
    assert [ev["stats"]["round"] for ev in _named(events, "sched.round")] == \
        list(range(1, out.n_rounds + 1))
    assert sum(ev["stats"]["rows"] for ev in _named(events, "sched.gen_step")) == \
        out.n_gen_tokens
    n_text = sum(1 for s in out.sessions for c in s.configs if c == TEXT)
    assert sum(ev["stats"]["tokens"] for ev in _named(events, "engine.recompute")) == \
        n_text * CHUNK


def test_logits_sync_picks_on_the_device(traced):
    """Every request is greedy, so each step's sync copies the device's ids:
    every ``sched.logits_sync`` span says so, as does the scheduler's count."""
    out, events = traced
    syncs = _named(events, "sched.logits_sync")
    assert syncs and all(ev["stats"]["pick"] == "device" for ev in syncs)
    assert out.n_device_picks == out.n_gen_steps == len(syncs)


def _decoded(out):
    """Bitstream chunks decoded per level, from the realized configs."""
    n = {}
    for s in out.sessions:
        for c in s.configs:
            if c != TEXT:
                n[c] = n.get(c, 0) + 1
    return n


def test_chunks_decoded_and_token_wall(traced):
    out, events = traced
    assert _decoded(out) == {0: 3, 1: 3}
    assert sum(ev["stats"]["n_chunks"] for ev in _named(events, "codec.parse")) == 6
    for tl in out.timeline:
        assert len(tl.token_wall) == len(tl.tokens_out) > 0
        assert tl.start_wall <= tl.token_wall[0]
        assert tl.token_wall == sorted(tl.token_wall)


def test_counters_agree_with_the_probe():
    from bench.cell import Cell
    from bench.tests.tiny import TINY, WORKLOAD

    cell = Cell(WORKLOAD, 5, lambda msg: None, overrides=TINY)
    cell.setup()
    rng = np.random.default_rng(7)
    n_tokens = 0
    for _ in range(2):
        n_calls = len(cell.probe.decode_calls)
        _, out, steps = cell._run_wave(cell._new_wave(rng))
        calls = cell.probe.decode_calls[n_calls:]
        decoded = _decoded(out)
        assert decoded.get(0, 0) == sum(n0 for n0, _ in calls) > 0
        assert sum(v for k, v in decoded.items() if k != 0) == \
            sum(n1 for _, n1 in calls)
        for tl in out.timeline:
            (row,) = set(tl.rows_used)
            stamps = [t for t, active in steps if active[row]]
            assert len(tl.token_wall) == len(tl.tokens_out) == len(stamps)
            for wall, stamp in zip(tl.token_wall, stamps):
                assert stamp <= wall <= stamp + 0.05
            n_tokens += len(stamps)
    assert n_tokens > 0
    cell.free_program()
