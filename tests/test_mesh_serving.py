"""Differential harness for the mesh-sharded serving engine (ISSUE 10).

Invariants:
  * mesh construction — ``make_serving_mesh`` / ``make_test_mesh`` raise a
    RuntimeError naming the exact ``XLA_FLAGS`` remediation and the current
    device census when the mesh does not fit the visible devices;
    ``make_serving_mesh(0)`` is a ValueError;
  * single-device rule no-op — entering ``sharding.use_rules`` on a
    one-device mesh leaves jit'd computations bit-identical to running
    outside any rules (the fallback must be a true no-op);
  * sharded row pool — blocked shard addressing, load-balanced allocation
    across shards, whole-shard divisibility errors, and exact degeneration
    to the base pool's lowest-free-row order at one shard;
  * mesh=1 — ``ShardedEngine`` is bit-identical to the plain ``Engine``
    through every primitive (``insert_runs`` / ``prefill_extend_rows`` /
    ``decode_step_rows`` / save-reset-restore), through ``ServeSession``,
    and through both schedulers (the ``ConcurrentScheduler`` wave and the
    ``ContinuousScheduler`` with generation and queueing);
  * mesh={2,4} (skipped below that many devices — CI's multi-device job
    forces 8 host devices) — per-request configs, TTFTs, caches and greedy
    tokens are bit-identical to the unsharded ``Engine`` oracle through
    both schedulers, admissions spread over every shard, the batch-1
    ``ServeSession`` fallback still matches, and a mid-generation
    suspend/resume on a sharded pool continues token-exactly.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import codec as kvcodec
from repro.launch.mesh import make_serving_mesh, make_test_mesh
from repro.models import sharding
from repro.serving.generation import GenerationSpec
from repro.serving.scheduler import (
    ConcurrentScheduler,
    ContinuousScheduler,
    PreemptionPolicy,
    RowPool,
    SessionRequest,
    ShardedRowPool,
)
from repro.serving.session import ServeSession
from repro.streaming import CacheGenStreamer, KVStore
from repro.streaming.network import BandwidthTrace, NetworkModel
from repro.streaming.pipeline import ContentionModel

T_CTX = 100
CHUNK = 20  # 5 chunks

N_DEV = len(jax.devices())

IDEAL = ContentionModel({1: 1.0, 2: 1.0})  # factor-1 at any N
SERIALIZED = ContentionModel({})  # factor(n) = n

needs = lambda n: pytest.mark.skipif(  # noqa: E731
    N_DEV < n,
    reason=f"needs {n} devices, have {N_DEV} (CI multi-device job sets "
    f"XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)


@pytest.fixture(scope="module")
def mfix():
    from repro.configs import registry
    from repro.models import build
    from repro.serving.engine import Engine
    from repro.serving.kv_layout import caches_to_codec_kv

    rng = np.random.default_rng(0)
    cfg = registry.get("smollm-360m").tiny()
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = Engine(cfg, params, cache_capacity=T_CTX + 48)
    tokens = rng.integers(0, cfg.vocab_size, size=(1, T_CTX)).astype(np.int32)
    logits, caches = eng.calculate_kv({"tokens": jnp.asarray(tokens)})
    kv = caches_to_codec_kv(caches, 0, T_CTX)
    ctab = kvcodec.profile([kv], kvcodec.CodecConfig(precision=10))
    store = KVStore(ctab)
    streamer = CacheGenStreamer(store, cfg)
    metas = store.store_kv("ctx", kv, chunk_tokens=CHUNK)
    u = sum(m.sizes[1] for m in metas) * 8 / 1e9  # level-1 ctx in 1 s
    first = int(jnp.argmax(logits[0, -1]))
    return dict(cfg=cfg, params=params, eng=eng, tokens=tokens, kv=kv,
                store=store, streamer=streamer, metas=metas, u=u,
                first=first, sharded={})


def _sharded(mfix, n):
    """ShardedEngine over an n-device ("data",) mesh, cached per module."""
    if n not in mfix["sharded"]:
        from repro.serving.mesh_engine import ShardedEngine

        mfix["sharded"][n] = ShardedEngine(
            mfix["cfg"], mfix["params"], cache_capacity=T_CTX + 48,
            mesh=make_serving_mesh(n),
        )
    return mfix["sharded"][n]


def _mk_session(mfix, eng, **kw):
    kw.setdefault("slo_s", 1.25)
    kw.setdefault("recompute_s", lambda t, p: 0.15 * 1.25 * t / CHUNK)
    kw.setdefault("decode_bytes_per_s", 1e9)
    kw.setdefault("max_run_tokens", 2 * CHUNK)
    return ServeSession(mfix["streamer"], eng, **kw)


def _requests(mfix, eng, traces, sess_kw=None, arrivals=None, specs=None):
    sess_kw = sess_kw or [{} for _ in traces]
    arrivals = arrivals if arrivals is not None else [0.0] * len(traces)
    specs = specs if specs is not None else [None] * len(traces)
    return [
        SessionRequest(
            _mk_session(mfix, eng, **kw), "ctx", mfix["tokens"],
            NetworkModel(tr), prior_throughput_gbps=float(tr.gbps[0]),
            start_t=arr, generation=spec,
        )
        for tr, kw, arr, spec in zip(traces, sess_kw, arrivals, specs)
    ]


def _kv_np(caches):
    return (
        np.asarray(caches.kv_k[:, :, :T_CTX], np.float32),
        np.asarray(caches.kv_v[:, :, :T_CTX], np.float32),
    )


def _oracle_tokens(mfix, caches, first, n):
    out = mfix["eng"].generate_with_kv(
        caches, jnp.asarray([first], jnp.int32), n
    )
    return out[0].tolist()


def _assert_results_bit_identical(a, b, what=""):
    """Per-request equality of two scheduler results (request order):
    decisions, TTFTs, caches, emitted tokens and their virtual times."""
    for i, (x, y) in enumerate(zip(a.sessions, b.sessions)):
        assert x.configs == y.configs, f"{what} req {i}: configs"
        assert abs(x.ttft_s - y.ttft_s) < 1e-12, f"{what} req {i}: ttft"
        for p, q in zip(_kv_np(x.caches), _kv_np(y.caches)):
            assert np.array_equal(p, q), f"{what} req {i}: caches differ"
    if hasattr(a, "timeline"):
        for i, (ta, tb) in enumerate(zip(a.timeline, b.timeline)):
            assert ta.tokens_out == tb.tokens_out, f"{what} req {i}: tokens"
            assert ta.token_ts == tb.token_ts, f"{what} req {i}: token_ts"


# ---------------------------------------------------------------------------
# mesh construction errors (satellite: actionable remediation)
# ---------------------------------------------------------------------------


def test_make_serving_mesh_rejects_nonpositive():
    with pytest.raises(ValueError, match="data >= 1"):
        make_serving_mesh(0)


def test_mesh_error_names_remediation_and_census():
    want = N_DEV + 1
    with pytest.raises(RuntimeError) as e:
        make_serving_mesh(want)
    msg = str(e.value)
    assert f"--xla_force_host_platform_device_count={want}" in msg
    assert "Remediation" in msg and "before" in msg
    assert f"{N_DEV} visible (" in msg  # the census, so the gap is obvious


def test_test_mesh_error_names_shape_and_axes():
    with pytest.raises(RuntimeError) as e:
        make_test_mesh(data=N_DEV, model=2)
    msg = str(e.value)
    assert f"({N_DEV}, 2)" in msg and "'data'" in msg and "'model'" in msg
    assert f"--xla_force_host_platform_device_count={2 * N_DEV}" in msg


def test_use_rules_single_device_is_true_noop():
    """Constraining under a one-device mesh must be the identity: tracing
    the same computation with and without the rules installed produces
    bit-identical outputs (fresh jit wrappers, so both really trace)."""
    mesh = make_serving_mesh(1)
    x = jnp.asarray(np.random.default_rng(7).normal(size=(4, 8)), jnp.float32)

    def body(a):
        return jnp.tanh(sharding.constrain(a, "cache_rows", None)) @ a.T

    base = jax.jit(body)(x)  # no rules: constrain is a documented no-op
    with sharding.use_rules(mesh):
        spec = sharding.logical_to_spec(("cache_rows",))
        ruled = jax.jit(body)(x)  # traced under the rules
    # the rule resolved to the mesh's one "data" axis (not dropped)...
    assert spec[0] is not None
    # ...and the computation is bit-identical anyway
    assert np.array_equal(np.asarray(base), np.asarray(ruled))


# ---------------------------------------------------------------------------
# sharded row pool
# ---------------------------------------------------------------------------


def test_base_pool_is_one_shard():
    pool = RowPool(5)
    assert pool.n_shards == 1 and pool.rows_per_shard == 5
    assert [pool.shard_of(r) for r in range(5)] == [0] * 5


def test_sharded_pool_blocked_addressing_and_balance():
    pool = ShardedRowPool(8, n_shards=4)
    assert pool.rows_per_shard == 2
    assert [pool.shard_of(r) for r in range(8)] == [0, 0, 1, 1, 2, 2, 3, 3]
    # allocation round-robins shards (load first, lowest row on ties)
    order = [pool.allocate(f"r{i}")[0] for i in range(8)]
    assert order == [0, 2, 4, 6, 1, 3, 5, 7]
    # releases re-balance: freeing both rows of shard 1 makes it the
    # least-loaded shard, so it takes the next two admissions
    pool.release(2, "r1", 10.0)
    pool.release(3, "r5", 11.0)
    assert pool.allocate("r8")[0] == 2
    assert pool.allocate("r9")[0] == 3


def test_sharded_pool_requires_whole_shards():
    with pytest.raises(ValueError, match="whole shards"):
        ShardedRowPool(6, n_shards=4)
    with pytest.raises(ValueError, match="n_shards >= 1"):
        ShardedRowPool(4, n_shards=0)


def test_sharded_pool_one_shard_degenerates_to_base():
    a, b = ShardedRowPool(4, n_shards=1), RowPool(4)
    ops = [("alloc", "x"), ("alloc", "y"), ("rel", 0, "x"), ("alloc", "z")]
    got = []
    for pool in (a, b):
        rows = []
        for op in ops:
            if op[0] == "alloc":
                rows.append(pool.allocate(op[1])[0])
            else:
                pool.release(op[1], op[2], 1.0)
        got.append(rows)
    assert got[0] == got[1] == [0, 1, 0]


# ---------------------------------------------------------------------------
# mesh=1: bit-identity to the plain Engine (runs in tier-1, single device)
# ---------------------------------------------------------------------------


def test_mesh1_primitives_bit_identical(mfix):
    """Every sharded primitive on an 8-row cache produces byte-identical
    caches (and active-row logits) to the plain Engine's."""
    eng, kv = mfix["eng"], mfix["kv"]
    se = _sharded(mfix, 1)
    assert se.n_shards == 1 and se.row_axis is not None
    assert se.cache_rows(5) == 5  # no rounding needed at one shard
    rng = np.random.default_rng(3)

    runs, rows, starts = (10, 14, 8), (1, 4, 6), (0, 0, 0)
    kv_new = kv[:, :, : sum(runs)]
    texts = rng.integers(0, mfix["cfg"].vocab_size, size=(8, 6)).astype(
        np.int32
    )
    widths = np.array([0, 6, 0, 0, 6, 0, 6, 0])
    toks = rng.integers(0, mfix["cfg"].vocab_size, size=(8, 1)).astype(
        np.int32
    )
    active = np.array([False, True, False, False, True, False, True, False])

    outs = []
    for e in (eng, se):
        caches = e.empty_caches(8)
        caches = e.insert_runs(caches, kv_new, rows, starts, runs)
        lg_x, caches = e.prefill_extend_rows(jnp.asarray(texts), caches, widths)
        lg_d, caches = e.decode_step_rows(jnp.asarray(toks), caches, active)
        snap = e.save_row(caches, 4, int(caches.length[4]))
        caches = e.reset_rows(caches, [4])
        caches = e.restore_row(caches, snap, 2)
        outs.append((caches, lg_x, lg_d))
    (ca, xa, da), (cb, xb, db) = outs
    assert np.array_equal(np.asarray(ca.kv_k), np.asarray(cb.kv_k))
    assert np.array_equal(np.asarray(ca.kv_v), np.asarray(cb.kv_v))
    assert np.array_equal(np.asarray(ca.length), np.asarray(cb.length))
    sel = widths > 0
    assert np.array_equal(np.asarray(xa)[sel], np.asarray(xb)[sel])
    assert np.array_equal(np.asarray(da)[active], np.asarray(db)[active])


def test_mesh1_serve_session_bit_identical(mfix):
    trace = BandwidthTrace.steps(0.2, [1.0 * mfix["u"], 0.55 * mfix["u"]])
    runs = [
        _mk_session(mfix, e).run("ctx", mfix["tokens"], NetworkModel(trace))
        for e in (mfix["eng"], _sharded(mfix, 1))
    ]
    a, b = runs
    assert a.configs == b.configs
    assert abs(a.ttft_s - b.ttft_s) < 1e-12
    for p, q in zip(_kv_np(a.caches), _kv_np(b.caches)):
        assert np.array_equal(p, q)


def test_mesh1_schedulers_bit_identical(mfix):
    """The full serving stack — wave scheduler, then continuous admission
    with queueing + generation under evolving (serialized) contention — is
    bit-identical on a one-device mesh."""
    u, first = mfix["u"], mfix["first"]
    traces = [
        BandwidthTrace.constant(3 * u),
        BandwidthTrace.steps(0.2, [1.0 * u, 0.55 * u]),
        BandwidthTrace.constant(50 * u),
    ]
    specs = [GenerationSpec(6, first), None, GenerationSpec(4, first)]
    arrivals = [0.0, 0.05, 0.3]

    wave = [
        ConcurrentScheduler(e, contention=SERIALIZED).run(
            _requests(mfix, e, traces)
        )
        for e in (mfix["eng"], _sharded(mfix, 1))
    ]
    _assert_results_bit_identical(wave[0], wave[1], "wave")

    cont = [
        ContinuousScheduler(
            e, rows=2, contention=SERIALIZED, gen_step_s=0.01
        ).run(
            _requests(mfix, e, traces, arrivals=arrivals, specs=specs)
        )
        for e in (mfix["eng"], _sharded(mfix, 1))
    ]
    a, b = cont
    _assert_results_bit_identical(a, b, "continuous")
    assert a.n_rounds == b.n_rounds
    assert a.gen_occupancy == b.gen_occupancy
    assert [t.admit_t for t in a.timeline] == [t.admit_t for t in b.timeline]
    # the scenario really generated and really queued
    assert a.n_gen_tokens == 10 and any(t.queue_wait_s > 0 for t in a.timeline)


# ---------------------------------------------------------------------------
# mesh={2,4}: the sharded path vs the unsharded oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "n_shards", [pytest.param(2, marks=needs(2)), pytest.param(4, marks=needs(4))]
)
def test_meshN_schedulers_match_unsharded_oracle(mfix, n_shards):
    """2S staggered requests (half generating) through both schedulers on a
    mesh of S: per-request decisions, TTFTs, caches and greedy tokens equal
    the plain Engine run's, and admissions land on every shard.  Contention
    is pinned ideal so sharded pricing (a pure perf term) cannot move
    decisions — what's under test is the sharded compute path."""
    u, first = mfix["u"], mfix["first"]
    se = _sharded(mfix, n_shards)
    assert se.n_shards == n_shards
    assert se.cache_rows(n_shards + 1) == 2 * n_shards
    n = 2 * n_shards
    traces = [
        BandwidthTrace.constant((3 + (i % 3)) * u) for i in range(n)
    ]
    kw = [dict(fixed_level=0) for _ in range(n)]
    specs = [GenerationSpec(5, first) if i % 2 else None for i in range(n)]
    arrivals = [0.02 * i for i in range(n)]

    wave = [
        ConcurrentScheduler(e, contention=IDEAL).run(
            _requests(mfix, e, traces, sess_kw=kw)
        )
        for e in (mfix["eng"], se)
    ]
    _assert_results_bit_identical(wave[0], wave[1], f"wave S={n_shards}")

    runs = [
        ContinuousScheduler(e, contention=IDEAL, gen_step_s=0.01).run(
            _requests(mfix, e, traces, sess_kw=kw, arrivals=arrivals,
                      specs=specs)
        )
        for e in (mfix["eng"], se)
    ]
    plain, shard = runs
    _assert_results_bit_identical(plain, shard, f"continuous S={n_shards}")
    # emitted streams also equal the greedy oracle on the final caches
    for i, spec in enumerate(specs):
        if spec is not None:
            want = _oracle_tokens(mfix, shard.sessions[i].caches, first, 5)
            assert shard.timeline[i].tokens_out == want, f"req {i}"
    # the balanced pool really spread the wave over every shard
    rows_per_shard = shard.n_rows // n_shards
    touched = {
        r // rows_per_shard for t in shard.timeline for r in t.rows_used
    }
    assert touched == set(range(n_shards))


@needs(2)
def test_mesh2_serve_session_falls_back_bit_identical(mfix):
    """A batch-1 ServeSession cache cannot split over 2 shards: the engine
    must transparently fall back to the single-device callables and still
    match the plain Engine byte-for-byte."""
    se = _sharded(mfix, 2)
    trace = BandwidthTrace.steps(0.15, [2.0 * mfix["u"], 0.4 * mfix["u"]])
    a, b = [
        _mk_session(mfix, e).run("ctx", mfix["tokens"], NetworkModel(trace))
        for e in (mfix["eng"], se)
    ]
    assert a.configs == b.configs
    assert abs(a.ttft_s - b.ttft_s) < 1e-12
    for p, q in zip(_kv_np(a.caches), _kv_np(b.caches)):
        assert np.array_equal(p, q)


@needs(2)
def test_mesh2_suspend_resume_crosses_shards_bit_exact(mfix):
    """Sharded pool, rows=2 (one per shard), both rows *generating* when a
    tight-deadline load arrives: the least-work victim (A, fewest emitted
    tokens) suspends mid-stream; A then takes the other generator's row —
    a resume that crosses the shard boundary through the sharded
    save/reset/restore path — and the displaced generator later resumes on
    A's old shard.  Both token streams still equal the greedy oracle's."""
    u, first = mfix["u"], mfix["first"]
    se = _sharded(mfix, 2)
    out = ContinuousScheduler(
        se, rows=2, contention=IDEAL, gen_step_s=0.05,
        preemption=PreemptionPolicy(victim="least_work"),
    ).run(_requests(
        mfix,
        se,
        [BandwidthTrace.constant(3 * u),    # A: slower load -> fewer emitted
         BandwidthTrace.constant(6 * u),    # C: quick load, long generation
         BandwidthTrace.constant(50 * u)],  # B: arrives mid-generation
        sess_kw=[dict(fixed_level=0), dict(fixed_level=0),
                 dict(fixed_level=0, slo_s=0.6)],
        arrivals=[0.0, 0.0, 0.55],
        specs=[GenerationSpec(10, first), GenerationSpec(12, first), None],
    ))
    assert out.n_preemptions >= 1 and out.n_resumes >= 1
    victim = out.timeline[0]
    # preempted *during* generation, resumed, and finished token-exactly
    assert victim.preempt_ts and victim.preempt_ts[0] > victim.finish_t
    emitted_before = sum(
        1 for ts in victim.token_ts if ts <= victim.preempt_ts[0]
    )
    assert 0 < emitted_before < 10
    for i, n in ((0, 10), (1, 12)):
        want = _oracle_tokens(mfix, out.sessions[i].caches, first, n)
        assert out.timeline[i].tokens_out == want, f"req {i}"
    # the victim's resume landed on the *other* shard's row
    rows_per_shard = out.n_rows // 2
    assert {r // rows_per_shard for r in victim.rows_used} == {0, 1}
    assert out.sessions[2].ttft_s < 0.6  # the preemptor met its SLO
    assert all(s.status == "ok" for s in out.sessions)


@needs(4)
def test_chip_smoke_mesh_phase_tiny():
    """``chip_smoke.py --chips 4``'s comparison at the tiny config on host
    devices: rows spread over four shards, and every request's tokens and
    cache equal the plain engine's bit for bit."""
    import chip_smoke
    from repro.configs import registry

    out = chip_smoke.mesh_phase(
        registry.get("smollm-360m-tiny"), n_chips=4, ctx_len=100,
        chunk_tokens=CHUNK, capacity=128, gen_tokens=4,
    )
    assert "cache rows spread over 4 devices" in out["checks"]
    assert "requests served on all 4 row shards" in out["checks"]
    for i in range(8):
        assert f"req {i} sharded cache equals plain bit for bit" in out["checks"]
