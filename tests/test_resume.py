"""Byte-range resumable fetch (ISSUE 8).

Covers the salvage stack end to end:
  * derived segment view of a packed chunk — head / anchor / delta runs
    tile the blob, each with its own CRC; ``verified_prefix`` turns any
    byte prefix into a resume offset (truncation bounds it, corruption of
    a *complete* segment raises ``IntegrityError``); the index survives
    its wire form;
  * ``synthesize_head`` rebuilds a level's head bytes from header fields
    alone, so a salvaged fine-level anchor composes with a coarser
    level's delta suffix into the coarse blob *byte-identically*;
  * ``(offset, length)`` byte-range fetches on sim and local transports;
    ``FetchHandle.cancel`` returns the realized, verifiable prefix; a
    ``truncate`` fault attaches its salvage to the ``FetchError``;
  * session integration: truncate faults are resumed from the verified
    prefix with exact per-chunk ``salvaged + refetched == wire``
    reconciliation and strictly fewer refetched bytes than the PR 6
    whole-blob baseline; zero faults leave the resume-armed session
    bit-identical; a preempted fetch's prefix survives suspend/resume;
    a mid-chunk bandwidth collapse triggers cancel -> salvage -> re-plan
    and the degraded session meets the SLO a pinned session misses;
  * property tests (`tests/_hyp` shim): random truncation points always
    yield verified segments or a clean ``IntegrityError``; random lossy
    level pairs compose bit-identically;
  * tcp (slow-marked): range + index over the socket protocol, connection
    pooling across attempts, stale-socket reconnect accounting, and
    server-side truncation salvage.
"""
import socket

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import bitstream
from repro.core import codec as kvcodec
from repro.serving.session import ServeSession, SessionTask, _ExecState
from repro.streaming import (
    CacheGenStreamer,
    FaultPlan,
    FaultyTransport,
    FetchError,
    KVStore,
    LocalTransport,
    RetryPolicy,
    SimTransport,
)
from repro.streaming.network import BandwidthTrace, NetworkModel
from repro.streaming.streamer import FetchPlan

from tests._hyp import given, settings, st

T_CTX = 100
CHUNK = 20  # 5 chunks

_ASSETS = None


def _assets():
    """Module-level lazy build (shared with the zero-arg `_hyp` fallback)."""
    global _ASSETS
    if _ASSETS is None:
        from repro.configs import registry
        from repro.models import build
        from repro.serving.engine import Engine
        from repro.serving.kv_layout import caches_to_codec_kv

        rng = np.random.default_rng(0)
        cfg = registry.get("smollm-360m").tiny()
        model = build(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        eng = Engine(cfg, params, cache_capacity=T_CTX + 40)
        tokens = rng.integers(0, cfg.vocab_size, size=(1, T_CTX)).astype(np.int32)
        _, caches = eng.calculate_kv({"tokens": jnp.asarray(tokens)})
        kv = caches_to_codec_kv(caches, 0, T_CTX)
        ctab = kvcodec.profile([kv], kvcodec.CodecConfig(precision=10))
        store = KVStore(ctab)
        streamer = CacheGenStreamer(store, cfg)
        metas = store.store_kv("ctx", kv, chunk_tokens=CHUNK)
        u = sum(m.sizes[1] for m in metas) * 8 / 1e9
        _ASSETS = dict(cfg=cfg, eng=eng, tokens=tokens, kv=kv, ctab=ctab,
                       store=store, streamer=streamer, metas=metas, u=u)
    return _ASSETS


@pytest.fixture(scope="module")
def rfix():
    return _assets()


# expensive recompute: TEXT is never first-feasible, so chunks actually ride
# the fetch path instead of short-circuiting to recompute
_R_SLOW = lambda t, p: 100.0  # noqa: E731


def _mk_session(fx, **kw) -> ServeSession:
    return ServeSession(
        fx["streamer"], fx["eng"], slo_s=1.0, recompute_s=kw.pop("rc", _R_SLOW),
        decode_bytes_per_s=1e9, **kw,
    )


def _oracle_close(fx, res):
    """Realized cache must match a clean rebuild of the same plan."""
    plan = FetchPlan(context_id="ctx", result=res.stream_result(),
                     metas=fx["metas"])
    ref = fx["streamer"].materialize(plan, fx["eng"], fx["tokens"],
                                     batch=1, fused=False)
    for a, b in ((res.caches.kv_k, ref.kv_k), (res.caches.kv_v, ref.kv_v)):
        np.testing.assert_allclose(
            np.asarray(a[:, :, :T_CTX], np.float32),
            np.asarray(b[:, :, :T_CTX], np.float32),
            atol=2e-2, rtol=2e-2,
        )


def _reconcile(res):
    """Per-chunk and per-task wire ledger: salvaged + refetched == wire."""
    for tl in res.timelines:
        if tl.wire_bytes > 0:
            assert abs(tl.salvaged_bytes + tl.refetched_bytes - tl.wire_bytes) \
                < 1e-6, (tl.chunk_idx, tl.salvaged_bytes, tl.refetched_bytes,
                         tl.wire_bytes)
    assert abs(res.salvaged_bytes + res.refetched_bytes - res.wire_bytes) < 1e-6


# ---------------------------------------------------------------------------
# segment layout (tentpole part 1: self-delimiting wire format)
# ---------------------------------------------------------------------------


def test_segment_index_tiles_blob_and_roundtrips_wire(rfix):
    blob = rfix["store"].get_kv("ctx", 0, 1)
    idx = bitstream.segment_index(blob)
    assert idx.total == len(blob)
    # segments tile [0, total) in order: head, anchor, delta+
    assert idx.segments[0].kind == "head" and idx.segments[0].start == 0
    assert idx.segments[1].kind == "anchor"
    assert all(s.kind == "delta" for s in idx.segments[2:])
    for a, b in zip(idx.segments, idx.segments[1:]):
        assert a.end == b.start
    assert idx.segments[-1].end == idx.total
    assert 0 < idx.head.end < idx.anchor_end < idx.total
    assert idx.n_arrays > 0
    # a whole, untouched blob verifies end to end
    assert idx.verified_prefix(blob) == idx.total
    # wire roundtrip (the index travels as fetch metadata, not blob bytes)
    again = bitstream.SegmentIndex.from_wire(idx.to_wire())
    assert again == idx
    with pytest.raises(bitstream.IntegrityError):
        bitstream.SegmentIndex.from_wire({"v": 1, "segs": "nope"})


def test_verified_prefix_truncation_vs_corruption(rfix):
    blob = rfix["store"].get_kv("ctx", 0, 1)
    idx = bitstream.segment_index(blob)
    # truncation mid-delta: everything up to the last whole segment stands
    cut = (idx.segments[2].start + idx.segments[2].end) // 2
    assert idx.verified_prefix(blob[:cut]) == idx.anchor_end
    # truncation mid-anchor: only the head stands
    assert idx.verified_prefix(blob[: idx.anchor_end - 1]) == idx.head.end
    # a complete-but-corrupt segment is an error, not a resume point
    bad = bytearray(blob)
    bad[idx.head.end + 5] ^= 0x40
    with pytest.raises(bitstream.IntegrityError, match="anchor"):
        idx.verified_prefix(bytes(bad))
    # suffix coordinates: data starting at a resume offset verifies too
    off = idx.anchor_end
    assert idx.verified_prefix(blob[off:], offset=off) == idx.total
    # a gap (offset not on the contiguous frontier) verifies nothing new
    assert idx.verified_prefix(blob[off + 1:], offset=off + 1) == off + 1


def test_synthesize_head_and_anchor_compose_bit_exact(rfix):
    store = rfix["store"]
    fine, coarse = store.get_kv("ctx", 0, 1), store.get_kv("ctx", 0, 2)
    i_f, i_c = bitstream.segment_index(fine), bitstream.segment_index(coarse)
    # synthesized head == packed head bytes, per level
    for blob, idx in ((fine, i_f), (coarse, i_c)):
        hdr = kvcodec.peek_chunk_header(blob)
        assert bitstream.synthesize_head(hdr, idx.n_arrays) \
            == blob[: idx.head.end]
    # lossy levels share the anchor bytes (a.* + scales) verbatim
    assert fine[i_f.head.end:i_f.anchor_end] \
        == coarse[i_c.head.end:i_c.anchor_end]
    # degrade-compose, exactly as the session does it: peek the FINE
    # salvage's header, swap the level, synthesize the coarse head, then
    # fine anchor + coarse delta suffix == the coarse blob byte-for-byte
    hdr = kvcodec.peek_chunk_header(fine)
    hdr["level"] = 2
    composed = (
        bitstream.synthesize_head(hdr, i_f.n_arrays)
        + fine[i_f.head.end:i_f.anchor_end]
        + coarse[i_c.anchor_end:]
    )
    assert composed == coarse
    assert kvcodec.verify_chunk(composed) is True


# ---------------------------------------------------------------------------
# transport byte ranges + cancel salvage (tentpole part 2)
# ---------------------------------------------------------------------------


def test_range_fetch_sim_and_local(rfix):
    full = rfix["store"].get_kv("ctx", 0, 1)
    off = 1000
    net = NetworkModel(BandwidthTrace.constant(400 * rfix["u"]))
    for t in (SimTransport(rfix["store"], net), LocalTransport(rfix["store"])):
        assert t.supports_range
        res = t.fetch_run(
            "ctx", [(0, 1)], byte_range=(off, None), resumable=True
        ).result(timeout=30)
        assert res.blobs[0] == full[off:]
        assert res.nbytes == len(full) - off  # the suffix is what's priced
        assert res.range_offset == off and res.range_total == len(full)
        assert res.seg_index is not None and res.seg_index.total == len(full)
        # bounded length + clamping
        res = t.fetch_run(
            "ctx", [(0, 1)], byte_range=(off, 500)
        ).result(timeout=30)
        assert res.blobs[0] == full[off:off + 500]
        with pytest.raises(ValueError, match="single-chunk"):
            t.fetch_run("ctx", [(0, 1), (1, 1)], byte_range=(0, 10))


def test_sim_cancel_returns_verified_salvage(rfix):
    full = rfix["store"].get_kv("ctx", 0, 1)
    # the whole level-1 context takes ~1s on this trace -> chunk 0 ~0.2s
    net = NetworkModel(BandwidthTrace.constant(rfix["u"]))
    t = SimTransport(rfix["store"], net)
    h = t.fetch_run("ctx", [(0, 1)], resumable=True)
    salv = h.cancel(0.1)
    assert salv is not None and 0 < len(salv.data) < len(full)
    assert salv.data == full[: len(salv.data)]  # a true prefix
    assert salv.offset == 0 and salv.total == len(full)
    assert salv.nbytes_wire > 0
    ve = salv.index.verified_prefix(salv.data)
    assert 0 < ve <= len(salv.data)


def test_truncate_fault_attaches_salvage(rfix):
    plan = FaultPlan(seed=5, truncate_p=1.0)
    net = NetworkModel(BandwidthTrace.constant(400 * rfix["u"]))
    ft = FaultyTransport(SimTransport(rfix["store"], net), plan)
    assert ft.supports_range  # mirrors the inner transport
    full = rfix["store"].get_kv("ctx", 0, 1)
    with pytest.raises(FetchError) as ei:
        ft.fetch_run("ctx", [(0, 1)], resumable=True).result(timeout=30)
    salv = ei.value.salvage
    assert salv is not None and 0 < len(salv.data) < len(full)
    assert salv.data == full[: len(salv.data)]
    # the keyed fraction is >= 0.25, which always covers head + anchor here
    assert salv.index.verified_prefix(salv.data) >= salv.index.anchor_end
    assert ft.n_injected["truncate"] == 1


# ---------------------------------------------------------------------------
# session: resume / compose / reconcile (tentpole part 3)
# ---------------------------------------------------------------------------


def _truncated_run(fx, *, resume: bool):
    plan = FaultPlan(seed=42, truncate_p=0.6)
    net = NetworkModel(BandwidthTrace.constant(400 * fx["u"]))
    ft = FaultyTransport(SimTransport(fx["store"], net), plan)
    res = _mk_session(
        fx,
        retry_policy=RetryPolicy(max_attempts=3, backoff_s=0.01, timeout_s=0.5),
        resume_fetch=resume,
    ).run("ctx", fx["tokens"], net, transport=ft)
    return res, ft


def test_session_truncate_resume_reconciles_and_lands_clean(rfix):
    res, ft = _truncated_run(rfix, resume=True)
    assert res.status == "ok" and int(res.caches.length[0]) == T_CTX
    assert ft.n_injected["truncate"] > 0
    assert res.n_resumes > 0 and res.salvaged_bytes > 0
    _reconcile(res)
    resumed = [tl for tl in res.timelines if tl.resumed]
    assert resumed and all(tl.salvaged_bytes > 0 for tl in resumed)
    _oracle_close(rfix, res)


def test_resume_strictly_beats_whole_blob_retry(rfix):
    res, _ = _truncated_run(rfix, resume=True)
    base, _ = _truncated_run(rfix, resume=False)
    assert base.status == "ok"
    # the baseline measures the wire but never salvages
    assert base.n_resumes == 0 and base.salvaged_bytes == 0
    _reconcile(base)
    # identical fault plan -> resume refetches strictly fewer bytes and
    # finishes no later
    assert res.refetched_bytes < base.refetched_bytes
    assert res.ttft_s <= base.ttft_s + 1e-9


def test_zero_fault_resume_armed_is_bit_identical(rfix):
    trace = BandwidthTrace.steps(0.2, [2.0 * rfix["u"], 0.6 * rfix["u"]])
    rc = lambda t, p: 0.04 * t / CHUNK  # noqa: E731
    base = _mk_session(rfix, rc=rc).run(
        "ctx", rfix["tokens"], NetworkModel(trace)
    )
    armed = _mk_session(
        rfix, rc=rc,
        retry_policy=RetryPolicy(max_attempts=3, timeout_s=10.0),
        replan_factor=None,  # virtual-clock replanning off by default
    ).run("ctx", rfix["tokens"], NetworkModel(trace))
    assert armed.status == "ok"
    assert armed.n_resumes == 0 and armed.n_mid_chunk_replans == 0
    assert armed.salvaged_bytes == 0
    assert armed.configs == base.configs
    assert abs(armed.ttft_s - base.ttft_s) < 1e-12
    for a, b in zip(
        (armed.caches.kv_k, armed.caches.kv_v),
        (base.caches.kv_k, base.caches.kv_v),
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # the ledger still ran: every fetched byte is accounted as refetched
    assert armed.wire_bytes > 0
    _reconcile(armed)


def test_preempted_fetch_prefix_survives_suspend_resume(rfix):
    sess = _mk_session(
        rfix, retry_policy=RetryPolicy(max_attempts=3, timeout_s=10.0)
    )
    net = NetworkModel(BandwidthTrace.constant(rfix["u"]))  # chunk ~0.2s
    task = SessionTask(sess, "ctx", rfix["tokens"], net,
                       transport=SimTransport(rfix["store"], net))
    caches = rfix["eng"].empty_caches(1)
    state = _ExecState()
    while task._pending is None:  # first step decides + issues chunk 0
        for w in task.step():
            caches = sess._execute_one(w, caches, state)
    task.suspend(0.1)  # mid-transfer: ~half the chunk realized
    sv = task._salvage
    assert sv is not None and sv.verified_end > 0
    assert task.salvaged_bytes == 0  # credited only when the chunk lands
    task.resume(0, 0.15)
    while not task.done:
        for w in task.step():
            caches = sess._execute_one(w, caches, state)
    res = task.result(caches, wall_total_s=0.0, n_runs=state.runs)
    assert res.status == "ok" and int(res.caches.length[0]) == T_CTX
    assert res.salvaged_bytes > 0 and res.n_resumes >= 1
    _reconcile(res)
    _oracle_close(rfix, res)


def test_mid_chunk_collapse_replans_and_meets_slo(rfix):
    # link collapses 1000x at t=1ms: chunk 0 lands clean at 2 Gbps, chunk 1
    # straddles the cliff -> realized duration blows past 3x the estimate,
    # the in-flight fetch is cancelled, its prefix salvaged, and the
    # remainder re-decided against the collapsed estimator
    trace = BandwidthTrace.steps(0.001, [2.0, 0.002])
    rc = lambda t, p: 0.3  # noqa: E731  TEXT infeasible before the collapse
    res = _mk_session(
        rfix, rc=rc,
        retry_policy=RetryPolicy(max_attempts=3, backoff_s=0.05, timeout_s=50.0),
        replan_factor=3.0,
    ).run("ctx", rfix["tokens"], NetworkModel(trace, rtt_s=0.0005),
          prior_throughput_gbps=2.0)
    assert res.status == "ok" and int(res.caches.length[0]) == T_CTX
    assert res.n_mid_chunk_replans >= 1
    assert any(tl.replanned for tl in res.timelines)
    _reconcile(res)
    assert not res.slo_violated  # adaptation absorbs the collapse
    _oracle_close(rfix, res)


def test_replan_meets_slo_that_pinned_config_misses(rfix):
    # a ~3800x collapse sized so the remaining *level-0* bytes overshoot
    # the SLO but the coarsest level still fits: the replanning session
    # cancels the straddling level-0 fetch and re-plans the remainder at
    # the coarsest level against the collapsed estimate; the pinned
    # level-0 session just keeps paying full-fat prices and misses
    trace = BandwidthTrace.steps(0.001, [2.0, 0.00053])
    rc = lambda t, p: 0.3  # noqa: E731  TEXT never feasible
    res = _mk_session(
        rfix, rc=rc,
        retry_policy=RetryPolicy(max_attempts=3, backoff_s=0.05, timeout_s=50.0),
        replan_factor=3.0,
    ).run("ctx", rfix["tokens"], NetworkModel(trace, rtt_s=0.0005),
          prior_throughput_gbps=2.0)
    assert res.status == "ok" and int(res.caches.length[0]) == T_CTX
    assert res.n_mid_chunk_replans >= 1
    assert not res.slo_violated
    _reconcile(res)
    _oracle_close(rfix, res)
    pinned = _mk_session(rfix, rc=rc, fixed_level=0).run(
        "ctx", rfix["tokens"], NetworkModel(trace, rtt_s=0.0005),
        prior_throughput_gbps=2.0,
    )
    assert pinned.slo_violated and pinned.ttft_s > res.ttft_s


# ---------------------------------------------------------------------------
# property tests (`tests/_hyp` shim)
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(
    level=st.integers(0, 4),
    frac=st.floats(0.0, 1.0),
    corrupt=st.booleans(),
    poke=st.floats(0.0, 1.0),
)
def test_prop_truncation_verifies_or_errors_never_lies(level, frac, corrupt,
                                                       poke):
    fx = _assets()
    level = level % fx["ctab"].config.n_levels
    blob = fx["store"].get_kv("ctx", 1, level)
    idx = bitstream.segment_index(blob)
    cut = int(frac * len(blob))
    ve = idx.verified_prefix(blob[:cut])
    # never past the cut, always on a segment boundary
    assert ve <= cut
    assert ve in {0} | {s.end for s in idx.segments}
    # every byte it vouches for is the true blob prefix (re-verifiable)
    assert idx.verified_prefix(blob[:ve]) == ve
    if corrupt and ve > 0:
        # flip one byte inside the verified range: a complete-but-corrupt
        # segment must raise, never silently resume past garbage
        bad = bytearray(blob[:cut])
        bad[int(poke * (ve - 1))] ^= 0x01
        with pytest.raises(bitstream.IntegrityError):
            idx.verified_prefix(bytes(bad))


@settings(max_examples=20, deadline=None)
@given(
    fine=st.integers(1, 8),
    coarse=st.integers(1, 8),
    chunk=st.integers(0, 4),
)
def test_prop_lossy_level_pairs_compose_bit_identical(fine, coarse, chunk):
    fx = _assets()
    n = fx["ctab"].config.n_levels
    lossy = list(range(1, n))
    fine, coarse = lossy[fine % len(lossy)], lossy[coarse % len(lossy)]
    f = fx["store"].get_kv("ctx", chunk, fine)
    c = fx["store"].get_kv("ctx", chunk, coarse)
    i_f, i_c = bitstream.segment_index(f), bitstream.segment_index(c)
    hdr = kvcodec.peek_chunk_header(f)
    hdr["level"] = coarse
    composed = (
        bitstream.synthesize_head(hdr, i_f.n_arrays)
        + f[i_f.head.end:i_f.anchor_end]
        + c[i_c.anchor_end:]
    )
    assert composed == c
    ha, aa = bitstream.unpack(composed)
    hb, ab = bitstream.unpack(c)
    assert ha == hb and set(aa) == set(ab)
    for k in aa:
        assert np.array_equal(aa[k], ab[k])


# ---------------------------------------------------------------------------
# tcp: range + index over the wire, pooling, reconnects (slow-marked)
# ---------------------------------------------------------------------------


def _socket_or_skip():
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.bind(("127.0.0.1", 0))
        s.close()
    except OSError as e:  # sandboxed CI without loopback sockets
        pytest.skip(f"sockets unavailable: {e}")


@pytest.mark.slow
def test_tcp_range_fetch_pooling_and_reconnect(rfix):
    _socket_or_skip()
    from repro.streaming.transport import TcpStoreServer, TcpTransport

    full = rfix["store"].get_kv("ctx", 0, 1)
    server = TcpStoreServer(rfix["store"])
    try:
        t = TcpTransport.for_server(server)
        off = 1000
        res = t.fetch_run(
            "ctx", [(0, 1)], byte_range=(off, None), resumable=True
        ).result(timeout=30)
        assert res.blobs[0] == full[off:]
        assert res.range_offset == off and res.range_total == len(full)
        assert res.seg_index is not None
        assert res.seg_index.verified_prefix(full) == len(full)
        # second fetch rides the pooled connection, not a fresh dial
        t.fetch_run("ctx", [(1, 1)]).result(timeout=30)
        s = t.tier_stats()
        assert s["n_connects"] == 1 and s["n_pool_reuses"] >= 1
        # a pooled socket gone stale forces one reconnect + silent replay
        with t._pool_lock:
            for sock in t._pool:
                sock.close()
        res = t.fetch_run("ctx", [(2, 1)]).result(timeout=30)
        assert res.blobs[0] == rfix["store"].get_kv("ctx", 2, 1)
        assert t.tier_stats()["n_reconnects"] >= 1
    finally:
        server.close()


@pytest.mark.slow
def test_tcp_server_truncate_salvages_client_side(rfix):
    _socket_or_skip()
    from repro.streaming.transport import TcpStoreServer, TcpTransport

    plan = FaultPlan(seed=9, truncate_p=1.0)
    full = rfix["store"].get_kv("ctx", 0, 1)
    server = TcpStoreServer(rfix["store"], fault_plan=plan)
    try:
        t = TcpTransport.for_server(server)
        h = t.fetch_run("ctx", [(0, 1)], resumable=True)
        # the sever surfaces as a transport error; the realized prefix is
        # harvested from the handle, exactly as the session's retry does
        with pytest.raises((FetchError, ConnectionError, OSError)):
            h.result(timeout=30)
        salv = h.salvage_at()
        assert salv is not None and 0 < len(salv.data) < len(full)
        assert salv.data == full[: len(salv.data)]
        assert salv.index is not None
        assert salv.index.verified_prefix(salv.data, salv.offset) > 0
        assert server.n_injected_faults >= 1
    finally:
        server.close()
