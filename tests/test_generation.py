"""Differential + property harness for continuous batched generation.

Invariants (ISSUE 9):
  * engine — ``Engine.decode_step_rows`` with one active row is
    token-identical to the ``generate_with_kv`` greedy oracle on that row's
    extracted cache, and inactive rows' KV / lengths are bit-preserved
    across stacked steps;
  * N=1 oracle identity — a lone request that loads then generates through
    the ``ContinuousScheduler`` emits exactly the oracle's greedy tokens,
    with strictly increasing virtual emission times and TPOT equal to the
    uncontended step cost;
  * load-only degeneration — ``generation=None`` and a zero-token
    ``GenerationSpec`` are bit-identical to each other (and therefore to
    the PR 8 load-only path): same decisions, TTFTs and caches, zero
    generation steps;
  * continuous batching — with staggered arrivals, generating rows and
    in-flight loads interleave on the shared engine and ready rows stack
    into one ``decode_step_rows`` dispatch (gen-occupancy width > 1), and
    the whole mixed wave is deterministic across runs;
  * suspend/resume — a generating row preempted mid-stream under the
    ``least_work`` victim policy resumes bit-exactly: final tokens equal
    the uninterrupted run's;
  * EDF admission — waiters are admitted by SLO deadline, FIFO by arrival;
  * cost-aware victim selection — ``_select_victim`` picks the
    least-realized-work candidate under ``least_work`` and the latest
    fetch-end straggler (first-wins ties) under the default policy;
  * greedy pick — ``Engine.greedy_pick``'s device ids equal ``np.argmax``
    of a float32 host copy, on random logits, on planted ties (the first
    index wins) and on row-sharded logits of a two-device ``ShardedEngine``;
    an all-greedy scheduler run picks on the device at every step and serves
    the tokens of the host-pick oracle, and a run that mixes greedy and
    seeded-sampling requests copies logits only on the steps that hold a
    sampled row, with every token as the host path gives it;
  * calibration — ``stacked_decode_step`` parses into gen contention
    factors and ``ContentionModel.gen_factor`` interpolates/falls back;
  * gen-SLO (ISSUE 10) — realized TPOT over ``GenerationSpec.gen_slo_s``
    accumulates per-token misses (suspension gaps included) that surface on
    ``RequestTimeline.gen_slo_miss``, and ``PreemptionPolicy(gen_slo=True)``
    makes an SLO-missing generating row evictable under the straggler rule
    with token-exact resumption.
"""
import json
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import codec as kvcodec
from repro.serving.generation import GenerationSpec, GenerationTask
from repro.serving.kv_layout import extract_row
from repro.serving.scheduler import (
    ContinuousScheduler,
    PreemptionPolicy,
    SessionRequest,
    _select_victim,
    _VictimCandidate,
)
from repro.serving.session import ServeSession
from repro.streaming import CacheGenStreamer, KVStore
from repro.streaming.network import BandwidthTrace, NetworkModel
from repro.streaming.pipeline import ContentionModel

T_CTX = 100
CHUNK = 20  # 5 chunks
GEN = 8
VOCAB = 49152  # smollm-360m's: the width the served path's pick reads
_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

IDEAL = ContentionModel({1: 1.0, 2: 1.0})  # factor-1 at any N


@pytest.fixture(scope="module")
def gfix():
    from repro.configs import registry
    from repro.models import build
    from repro.serving.engine import Engine
    from repro.serving.kv_layout import caches_to_codec_kv

    rng = np.random.default_rng(0)
    cfg = registry.get("smollm-360m").tiny()
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    # capacity leaves room for the context plus every generated token
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
    return dict(cfg=cfg, eng=eng, tokens=tokens, store=store,
                streamer=streamer, metas=metas, u=u, first=first)


def _mk_session(gfix, **kw):
    kw.setdefault("slo_s", 1.25)
    kw.setdefault("recompute_s", lambda t, p: 0.15 * 1.25 * t / CHUNK)
    kw.setdefault("decode_bytes_per_s", 1e9)
    kw.setdefault("max_run_tokens", 2 * CHUNK)
    return ServeSession(gfix["streamer"], gfix["eng"], **kw)


def _requests(gfix, traces, sess_kw=None, arrivals=None, specs=None):
    sess_kw = sess_kw or [{} for _ in traces]
    arrivals = arrivals if arrivals is not None else [0.0] * len(traces)
    specs = specs if specs is not None else [None] * len(traces)
    return [
        SessionRequest(
            _mk_session(gfix, **kw), "ctx", gfix["tokens"], NetworkModel(tr),
            prior_throughput_gbps=float(tr.gbps[0]), start_t=arr,
            generation=spec,
        )
        for tr, kw, arr, spec in zip(traces, sess_kw, arrivals, specs)
    ]


def _kv_np(caches):
    return (
        np.asarray(caches.kv_k[:, :, :T_CTX], np.float32),
        np.asarray(caches.kv_v[:, :, :T_CTX], np.float32),
    )


def _oracle_tokens(gfix, caches, first, n):
    """Greedy reference: generate_with_kv on the request's loaded cache."""
    out = gfix["eng"].generate_with_kv(
        caches, jnp.asarray([first], jnp.int32), n
    )
    return out[0].tolist()


def _host_pick_tokens(gfix, caches, first, n, task=None):
    """Host-pick reference on the request's loaded cache: one-row
    ``decode_step_rows`` steps, a float32 host copy of the last position's
    logits, then ``np.argmax`` (or ``task``'s seeded sample)."""
    tok = np.array([[first]], np.int32)
    out = []
    for _ in range(n):
        logits, caches = gfix["eng"].decode_step_rows(
            jnp.asarray(tok), caches, jnp.ones(1, bool)
        )
        row = np.asarray(logits[:, -1], np.float32)[0]
        tok[0, 0] = int(np.argmax(row)) if task is None else task.next_token(row)
        out.append(int(tok[0, 0]))
    return out


# ---------------------------------------------------------------------------
# engine: decode_step_rows vs the greedy oracle
# ---------------------------------------------------------------------------


def test_decode_step_rows_matches_oracle_and_preserves_inactive(gfix):
    """Six stacked steps with only row 1 active: row 1's argmax chain equals
    the generate_with_kv oracle on its extracted cache; rows 0/2 keep their
    KV bytes and lengths untouched (the where-merge must not leak)."""
    eng = gfix["eng"]
    rng = np.random.default_rng(3)
    caches = eng.empty_caches(3)
    toks = rng.integers(0, gfix["cfg"].vocab_size, size=(3, 32)).astype(np.int32)
    logits, caches = eng.prefill_extend_rows(
        jnp.asarray(toks), caches, np.full(3, 32)
    )
    ref_caches = extract_row(caches, 1)
    first = int(jnp.argmax(logits[1, 31]))
    want = _oracle_tokens(gfix, ref_caches, first, 6)

    k0 = np.asarray(caches.kv_k[:, 0], np.float32)
    k2 = np.asarray(caches.kv_k[:, 2], np.float32)
    active = np.array([False, True, False])
    tok = np.array([[0], [first], [0]], np.int32)
    got = []
    for _ in range(6):
        step_logits, caches = eng.decode_step_rows(
            jnp.asarray(tok), caches, jnp.asarray(active)
        )
        nxt = int(jnp.argmax(step_logits[1, -1]))
        got.append(nxt)
        tok[1, 0] = nxt
    assert got == want, (got, want)
    assert np.array_equal(np.asarray(caches.kv_k[:, 0], np.float32), k0)
    assert np.array_equal(np.asarray(caches.kv_k[:, 2], np.float32), k2)
    assert [int(x) for x in caches.length] == [32, 38, 32]


def test_decode_step_rows_validates_shapes(gfix):
    eng = gfix["eng"]
    caches = eng.empty_caches(2)
    with pytest.raises(ValueError, match="tokens"):
        eng.decode_step_rows(
            jnp.zeros((2, 3), jnp.int32), caches, jnp.ones(2, bool)
        )
    with pytest.raises(ValueError, match="active"):
        eng.decode_step_rows(
            jnp.zeros((2, 1), jnp.int32), caches, jnp.ones(3, bool)
        )


# ---------------------------------------------------------------------------
# engine: the greedy pick on the device
# ---------------------------------------------------------------------------


def _pick_logits(tied: bool) -> np.ndarray:
    """(8, 1, VOCAB) bfloat16 values held as float32.  ``tied`` plants the
    row's maximum at three indices (row 0 at both ends of the vocabulary)."""
    rng = np.random.default_rng(11)
    x = rng.standard_normal((8, 1, VOCAB)).astype(jnp.bfloat16).astype(np.float32)
    if tied:
        for r in range(8):
            at = [0, 77, VOCAB - 1] if r == 0 else rng.choice(VOCAB, 3, replace=False)
            x[r, 0, at] = 16.0
    return x


_PICK_ON_TWO_DEVICES = r"""
import json, sys
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import registry
from repro.launch.mesh import make_serving_mesh
from repro.models import build
from repro.serving.mesh_engine import ShardedEngine

cfg = registry.get("smollm-360m").tiny()
se = ShardedEngine(cfg, build(cfg).init_params(jax.random.PRNGKey(0)),
                   cache_capacity=32, mesh=make_serving_mesh(2))
given = jax.device_put(jnp.asarray(np.load(sys.argv[1]), jnp.bfloat16),
                       NamedSharding(se.mesh, P("data")))
toks = np.random.default_rng(5).integers(0, cfg.vocab_size, (4, 1)).astype(np.int32)
step, _ = se.decode_step_rows(jnp.asarray(toks), se.empty_caches(4), np.ones(4, bool))
print(json.dumps(dict(
    given=np.asarray(se.greedy_pick(given)).tolist(),
    step=np.asarray(se.greedy_pick(step)).tolist(),
    step_host=np.argmax(np.asarray(step[:, -1], np.float32), -1).tolist(),
    step_devices=len(step.sharding.device_set),
)))
"""


def _pick_on_two_devices(tmp_path, logits: np.ndarray) -> dict:
    """``ShardedEngine.greedy_pick`` over two forced host devices, in a
    child process (this one keeps its single device): on ``logits`` put
    row-sharded, and on a sharded ``decode_step_rows`` step's own logits."""
    path = tmp_path / "logits.npy"
    np.save(path, logits)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_REPO, "src"), env.get("PYTHONPATH", "")])
    out = subprocess.run(
        [sys.executable, "-c", _PICK_ON_TWO_DEVICES, str(path)], env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", ["random", "ties", "sharded2"])
def test_greedy_pick_equals_host_argmax(gfix, tmp_path, case):
    """The device pick equals ``np.argmax`` of the float32 host copy it
    replaces, bit for bit: on random bfloat16 logits, on planted ties (the
    first index wins), and on a two-device ShardedEngine's row-sharded
    logits, given and of its own stacked step."""
    logits = _pick_logits(tied=case != "random")
    want = np.argmax(logits[:, -1], axis=-1)
    if case == "sharded2":
        out = _pick_on_two_devices(tmp_path, logits)
        assert out["step_devices"] == 2
        assert out["step"] == out["step_host"]
        got = np.asarray(out["given"])
    else:
        ids = gfix["eng"].greedy_pick(jnp.asarray(logits, jnp.bfloat16))
        assert ids.dtype == jnp.int32 and ids.shape == (8,)
        got = np.asarray(ids)
    np.testing.assert_array_equal(got, want)
    if case != "random":
        assert got[0] == 0
        assert all(logits[r, 0, got[r]] == 16.0 for r in range(8))


# ---------------------------------------------------------------------------
# scheduler: N=1 oracle identity, load-only degeneration
# ---------------------------------------------------------------------------


def test_generation_n1_matches_greedy_oracle(gfix):
    u, first = gfix["u"], gfix["first"]
    spec = GenerationSpec(n_tokens=GEN, first_token=first)
    out = ContinuousScheduler(gfix["eng"], contention=IDEAL).run(
        _requests(gfix, [BandwidthTrace.constant(3 * u)],
                  sess_kw=[dict(fixed_level=0)], specs=[spec])
    )
    tl = out.timeline[0]
    want = _oracle_tokens(gfix, out.sessions[0].caches, first, GEN)
    assert tl.tokens_out == want
    assert tl.n_tokens_out == GEN
    assert out.n_gen_tokens == GEN and out.n_gen_steps == GEN
    # virtual timing: emissions strictly increase, start after the load,
    # and N=1 TPOT is exactly the uncontended step cost
    assert all(b > a for a, b in zip(tl.token_ts, tl.token_ts[1:]))
    assert tl.token_ts[0] > tl.finish_t
    assert tl.gen_finish_t == tl.token_ts[-1]
    assert tl.mean_tpot_s == pytest.approx(2e-3)
    assert max(n for _, n in out.gen_occupancy) == 1


def test_zero_token_spec_bit_identical_to_load_only(gfix):
    """generation=None and GenerationSpec(n_tokens=0) must be the same
    computation: decisions, TTFTs, caches, round count — and no generation
    machinery may run."""
    u, first = gfix["u"], gfix["first"]
    traces = [BandwidthTrace.constant(3 * u),
              BandwidthTrace.steps(0.2, [1.0 * u, 0.55 * u])]
    runs = []
    for specs in ([None, None],
                  [GenerationSpec(0, first), GenerationSpec(0, first)]):
        runs.append(ContinuousScheduler(gfix["eng"], contention=IDEAL).run(
            _requests(gfix, traces, specs=specs)
        ))
    a, b = runs
    assert a.n_rounds == b.n_rounds
    assert a.n_gen_steps == b.n_gen_steps == 0
    assert a.gen_occupancy == b.gen_occupancy == []
    for i, (x, y) in enumerate(zip(a.sessions, b.sessions)):
        assert x.configs == y.configs, f"req {i}"
        assert abs(x.ttft_s - y.ttft_s) < 1e-12
        for p, q in zip(_kv_np(x.caches), _kv_np(y.caches)):
            assert np.array_equal(p, q), f"req {i}: caches differ"
    for tl in b.timeline:
        assert tl.tokens_out == [] and np.isnan(tl.gen_finish_t)


# ---------------------------------------------------------------------------
# continuous batching: interleaving, stacking, determinism
# ---------------------------------------------------------------------------


def test_mixed_wave_stacks_generation_and_is_deterministic(gfix):
    """Four staggered arrivals on two rows, three of them generating: the
    generation steps interleave with in-flight loads, ready rows stack
    (occupancy width 2), every generating request matches its own oracle,
    and the whole run is bit-deterministic across executions."""
    u, first = gfix["u"], gfix["first"]

    def run_once():
        traces = [
            BandwidthTrace.constant(3 * u),
            BandwidthTrace.constant(2.5 * u),
            BandwidthTrace.constant(2 * u),
            BandwidthTrace.constant(3 * u),
        ]
        specs = [
            GenerationSpec(12, first),
            GenerationSpec(10, first),
            GenerationSpec(8, first),
            None,
        ]
        return ContinuousScheduler(
            gfix["eng"], rows=2, contention=IDEAL, gen_step_s=0.02,
        ).run(_requests(
            gfix, traces,
            sess_kw=[dict(fixed_level=0)] * 4,
            arrivals=[0.0, 0.02, 0.35, 0.4],
            specs=specs,
        ))

    a = run_once()
    b = run_once()
    assert [tl.tokens_out for tl in a.timeline] == \
           [tl.tokens_out for tl in b.timeline]
    assert [tl.token_ts for tl in a.timeline] == \
           [tl.token_ts for tl in b.timeline]
    assert a.gen_occupancy == b.gen_occupancy
    # ready generating rows actually stacked into one dispatch
    assert max(n for _, n in a.gen_occupancy) == 2
    # generation interleaved with loads: some step fired before the last
    # load finished
    last_load_finish = max(tl.finish_t for tl in a.timeline)
    assert min(t for t, _ in a.gen_occupancy) < last_load_finish
    for i, spec in enumerate([12, 10, 8]):
        want = _oracle_tokens(gfix, a.sessions[i].caches, first, spec)
        assert a.timeline[i].tokens_out == want, f"req {i}"
    assert a.timeline[3].tokens_out == []


def test_greedy_run_picks_on_device_and_matches_host_pick(gfix):
    """Three staggered greedy requests on two rows: every generation step
    picks on the device, ready rows stack, and each request serves exactly
    the host-pick oracle's tokens."""
    u, first = gfix["u"], gfix["first"]
    n_out = [12, 10, 8]
    out = ContinuousScheduler(
        gfix["eng"], rows=2, contention=IDEAL, gen_step_s=0.02,
    ).run(_requests(
        gfix, [BandwidthTrace.constant(k * u) for k in (3, 2.5, 2)],
        sess_kw=[dict(fixed_level=0)] * 3, arrivals=[0.0, 0.02, 0.35],
        specs=[GenerationSpec(n, first) for n in n_out],
    ))
    assert out.n_device_picks == out.n_gen_steps > 0
    assert max(n for _, n in out.gen_occupancy) == 2
    for i, n in enumerate(n_out):
        want = _host_pick_tokens(gfix, out.sessions[i].caches, first, n)
        assert out.timeline[i].tokens_out == want, f"req {i}"


def test_sampled_rows_take_the_host_pick_with_unchanged_tokens(gfix):
    """A greedy request and a seeded-sampling one generating side by side:
    exactly the steps that hold the sampled row copy logits to the host
    (some of them stacked with the greedy row), every other step picks on
    the device, and both requests serve the host path's tokens."""
    u, first = gfix["u"], gfix["first"]
    specs = [GenerationSpec(12, first), GenerationSpec(4, first, sample_seed=123)]
    out = ContinuousScheduler(
        gfix["eng"], rows=2, contention=IDEAL, gen_step_s=0.02,
    ).run(_requests(
        gfix, [BandwidthTrace.constant(3 * u), BandwidthTrace.constant(2.5 * u)],
        sess_kw=[dict(fixed_level=0)] * 2, arrivals=[0.0, 0.02], specs=specs,
    ))
    greedy, sampled = out.timeline
    host_steps = set(sampled.token_ts)  # one emission instant per step
    assert out.n_gen_steps - out.n_device_picks == len(host_steps) == 4
    assert out.n_device_picks > 0
    assert host_steps & set(greedy.token_ts)  # a host step stacked both rows
    assert greedy.tokens_out == _host_pick_tokens(
        gfix, out.sessions[0].caches, first, 12)
    task = GenerationTask(specs[1], index=1, label="req1:ctx", row=0,
                          start_t=0.0, context_tokens=T_CTX,
                          capacity=gfix["eng"].capacity)
    assert sampled.tokens_out == _host_pick_tokens(
        gfix, out.sessions[1].caches, first, 4, task=task)


def test_generation_charges_contention(gfix):
    """Under a serialized contention model two stacked rows pay factor 2 per
    virtual step; the same wave under the ideal model pays factor 1 — the
    virtual clock (and hence TPOT) must see decode pressure."""
    u, first = gfix["u"], gfix["first"]

    def run(contention):
        return ContinuousScheduler(
            gfix["eng"], rows=2, contention=contention, gen_step_s=0.01,
        ).run(_requests(
            gfix,
            [BandwidthTrace.constant(3 * u), BandwidthTrace.constant(3 * u)],
            sess_kw=[dict(fixed_level=0)] * 2,
            specs=[GenerationSpec(6, first), GenerationSpec(6, first)],
        ))

    ideal = run(IDEAL)
    serial = run(ContentionModel({}))
    # identical traces: both rows generate in lockstep, every step stacks 2
    assert max(n for _, n in ideal.gen_occupancy) == 2
    assert ideal.timeline[0].mean_tpot_s == pytest.approx(0.01)
    assert serial.timeline[0].mean_tpot_s == pytest.approx(0.02)
    # tokens themselves are timing-independent
    assert [tl.tokens_out for tl in serial.timeline] == \
           [tl.tokens_out for tl in ideal.timeline]


# ---------------------------------------------------------------------------
# suspend/resume mid-generation (least_work victim)
# ---------------------------------------------------------------------------


def test_suspend_resume_mid_generation_bit_exact(gfix):
    """rows=1: request A is mid-generation when tight-deadline B arrives;
    under victim=least_work A's row suspends (bit-exact RowSnapshot spanning
    context + emitted tokens), B loads and finishes, A resumes and its final
    token stream equals the uninterrupted solo run's."""
    u, first = gfix["u"], gfix["first"]
    spec = GenerationSpec(10, first)
    mk = lambda arrivals, traces, kw, specs, preemption: ContinuousScheduler(  # noqa: E731
        gfix["eng"], rows=1, contention=IDEAL, gen_step_s=0.05,
        preemption=preemption,
    ).run(_requests(gfix, traces, sess_kw=kw, arrivals=arrivals, specs=specs))

    solo = mk([0.0], [BandwidthTrace.constant(3 * u)],
              [dict(fixed_level=0)], [spec], None)
    want = solo.timeline[0].tokens_out
    assert want == _oracle_tokens(gfix, solo.sessions[0].caches, first, 10)
    t_fin = solo.timeline[0].finish_t

    out = mk(
        [0.0, t_fin + 0.13],
        [BandwidthTrace.constant(3 * u), BandwidthTrace.constant(50 * u)],
        [dict(fixed_level=0), dict(fixed_level=0)],
        [spec, None],
        PreemptionPolicy(victim="least_work"),
    )
    t0, t1 = out.timeline
    assert out.n_preemptions >= 1 and out.n_resumes >= 1
    # preempted *during* generation: after its own load finished, with some
    # but not all tokens already emitted
    assert t0.preempt_ts[0] > t0.finish_t
    emitted_before = sum(1 for ts in t0.token_ts if ts <= t0.preempt_ts[0])
    assert 0 < emitted_before < 10
    # B got the row promptly and met its SLO
    assert out.sessions[1].ttft_s < 1.25
    # bit-exact continuation
    assert t0.tokens_out == want
    assert t0.gen_finish_t > t1.finish_t


# ---------------------------------------------------------------------------
# EDF admission + cost-aware victim selection
# ---------------------------------------------------------------------------


def test_edf_admission_orders_waiters_by_deadline(gfix):
    """rows=1 with two queued arrivals: FIFO admits in arrival order; EDF
    admits the later, tighter-deadline waiter first."""
    u = gfix["u"]
    traces = [BandwidthTrace.constant(0.4 * u),  # r0 holds the row a while
              BandwidthTrace.constant(3 * u),
              BandwidthTrace.constant(3 * u)]
    kw = [dict(fixed_level=0),
          dict(fixed_level=0, slo_s=10.0),   # r1: early arrival, loose SLO
          dict(fixed_level=0, slo_s=0.5)]    # r2: later arrival, tight SLO
    arrivals = [0.0, 0.01, 0.02]

    def run(admission):
        return ContinuousScheduler(
            gfix["eng"], rows=1, contention=IDEAL, admission=admission,
        ).run(_requests(gfix, traces, sess_kw=kw, arrivals=arrivals))

    fifo = run("fifo")
    assert fifo.timeline[1].admit_t < fifo.timeline[2].admit_t
    edf = run("edf")
    assert edf.timeline[2].admit_t < edf.timeline[1].admit_t
    # both waiters queued behind r0 in both runs
    assert edf.timeline[2].admit_t == pytest.approx(edf.timeline[0].finish_t)


def test_select_victim_policies():
    mk = lambda end_t, work, is_gen=False: _VictimCandidate(  # noqa: E731
        obj=object(), is_gen=is_gen, end_t=end_t, preempt_t=0.0, work=work)
    straggler = PreemptionPolicy()
    least = PreemptionPolicy(victim="least_work")
    a, b, c = mk(5.0, 300), mk(9.0, 100), mk(9.0, 200, is_gen=True)
    # straggler: latest fetch end, first-wins on ties (PR 5 loop semantics)
    assert _select_victim(straggler, [a, b, c]) is b
    # least_work: fewest realized tokens regardless of kind
    assert _select_victim(least, [a, b, c]) is b
    assert _select_victim(least, [a, c]) is c
    assert _select_victim(least, []) is None
    with pytest.raises(ValueError, match="victim"):
        PreemptionPolicy(victim="coin_flip")


def test_scheduler_validates_knobs(gfix):
    with pytest.raises(ValueError, match="admission"):
        ContinuousScheduler(gfix["eng"], admission="lifo")
    with pytest.raises(ValueError, match="gen_step_s"):
        ContinuousScheduler(gfix["eng"], gen_step_s=0.0)


# ---------------------------------------------------------------------------
# spec/task validation
# ---------------------------------------------------------------------------


def test_generation_spec_and_task_validate():
    with pytest.raises(ValueError, match="n_tokens"):
        GenerationSpec(-1, 0)
    with pytest.raises(ValueError, match="gen_slo_s"):
        GenerationSpec(4, 0, gen_slo_s=0.0)
    with pytest.raises(ValueError, match="capacity"):
        GenerationTask(GenerationSpec(64, 0), index=0, label="req0:ctx",
                       row=0, start_t=0.0, context_tokens=100, capacity=128)
    t = GenerationTask(GenerationSpec(2, 7), index=0, label="req0:ctx",
                       row=0, start_t=0.0, context_tokens=100, capacity=128)
    t.record(5, 0.1)
    t.record(9, 0.2)
    assert t.done and t.realized_tokens == 102
    with pytest.raises(ValueError, match="already emitted"):
        t.suspend(0.3)


def test_seeded_sampling_is_deterministic_and_differs_from_greedy(gfix):
    u, first = gfix["u"], gfix["first"]

    def run(seed):
        return ContinuousScheduler(gfix["eng"], contention=IDEAL).run(
            _requests(gfix, [BandwidthTrace.constant(3 * u)],
                      sess_kw=[dict(fixed_level=0)],
                      specs=[GenerationSpec(GEN, first, sample_seed=seed)])
        ).timeline[0].tokens_out

    assert run(123) == run(123)
    greedy = ContinuousScheduler(gfix["eng"], contention=IDEAL).run(
        _requests(gfix, [BandwidthTrace.constant(3 * u)],
                  sess_kw=[dict(fixed_level=0)],
                  specs=[GenerationSpec(GEN, first)])
    ).timeline[0].tokens_out
    assert run(123) != greedy  # vanishingly unlikely to collide for 8 tokens


# ---------------------------------------------------------------------------
# contention: gen factor curve + calibration parsing
# ---------------------------------------------------------------------------


def test_gen_factor_interpolates_and_falls_back():
    both = ContentionModel({1: 1.0, 4: 3.0}, gen_factors={1: 1.0, 4: 2.0})
    assert both.gen_factor(4) == 2.0
    assert both.gen_factor(1) == 1.0
    assert both.gen_factor(2) == pytest.approx(4.0 / 3.0)  # interpolated
    decode_only = ContentionModel({1: 1.0, 4: 3.0})
    assert decode_only.gen_factor(4) == 3.0  # falls back to decode curve
    empty = ContentionModel({})
    assert empty.gen_factor(5) == 5.0  # serialized fallback of the fallback


def test_stacked_decode_step_calibration_parses(tmp_path, monkeypatch):
    from repro.streaming import calibration

    path = tmp_path / "BENCH_codec.json"
    path.write_text(json.dumps({
        "host_backend": jax.default_backend(),
        "fused": {"bytes_per_s": 1.0},
        "stacked_decode_step": {
            "1": {"batched": {"tokens_per_s": 100.0}},
            "4": {"batched": {"tokens_per_s": 250.0}},
            "8": {"batched": {"tokens_per_s": 1600.0}},  # super-linear: clamp
        },
    }))
    monkeypatch.setenv("CACHEGEN_BENCH_CODEC", str(path))
    calibration.clear_calibration_cache()
    try:
        factors = calibration.measured_generation_contention_factors()
        assert factors == {1: 1.0, 4: pytest.approx(1.6), 8: 1.0}
    finally:
        calibration.clear_calibration_cache()


# ---------------------------------------------------------------------------
# benchmark acceptance (separate CI job)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_generation_serving_bench_acceptance(tmp_path):
    """Reduced benchmarks/generation_serving.py run: continuous batching
    beats drain-then-generate on aggregate tokens/s, greedy tokens are
    oracle-identical, and the load-only path stays bit-identical."""
    import benchmarks.generation_serving as gs

    report = gs.run(out_path=str(tmp_path / "BENCH_generation.json"),
                    verbose=False)
    acc = report["acceptance"]
    assert acc["speedup_ge_1p5"] is True
    assert acc["greedy_tokens_match_oracle"] is True
    assert acc["load_only_bit_identical"] is True
    assert acc["generation_interleaved_with_loads"] is True
    assert report["batched_vs_drain"]["speedup"] >= 1.5


# ---------------------------------------------------------------------------
# per-token generation SLO (ISSUE 10)
# ---------------------------------------------------------------------------


def test_generation_spec_validates_gen_slo():
    with pytest.raises(ValueError, match="gen_slo_s"):
        GenerationSpec(4, 0, gen_slo_s=0.0)


def test_generation_task_gen_slo_accounting():
    """Realized TPOT over the SLO bumps slo_misses (suspension gaps count);
    resume() resets the since-resume progress counter, not the misses."""
    spec = GenerationSpec(5, 7, gen_slo_s=0.1)
    g = GenerationTask(spec, index=0, label="g", row=0, start_t=1.0,
                       context_tokens=10, capacity=64)
    g.record(3, 1.05)  # 0.05 <= 0.1: on time (measured from start_t)
    assert g.slo_misses == 0 and not g.slo_missed
    g.record(4, 1.30)  # 0.25 > 0.1: miss
    assert g.slo_misses == 1 and g.slo_missed
    assert g.tokens_since_resume == 2
    g.suspend(1.3)
    g.resume(1, 2.0)
    assert g.tokens_since_resume == 0 and g.slo_misses == 1
    g.record(5, 2.75)  # the 1.45 s gap includes the suspension: miss
    assert g.slo_misses == 2 and g.tokens_since_resume == 1


def test_gen_slo_misses_surface_on_timeline(gfix):
    """N=1 with the default 2e-3 step: a 1.5e-3 per-token SLO misses on
    every token; a loose one misses on none — and the counts land on the
    RequestTimeline and the result aggregate."""
    u, first = gfix["u"], gfix["first"]
    for slo, want in ((1.5e-3, GEN), (1.0, 0)):
        out = ContinuousScheduler(gfix["eng"], contention=IDEAL).run(
            _requests(gfix, [BandwidthTrace.constant(3 * u)],
                      sess_kw=[dict(fixed_level=0)],
                      specs=[GenerationSpec(GEN, first, gen_slo_s=slo)])
        )
        assert out.timeline[0].gen_slo_miss == want
        assert out.n_gen_slo_miss == want
        assert out.timeline[0].n_tokens_out == GEN  # flagged, never truncated


def test_gen_slo_makes_straggler_policy_preempt_generation(gfix):
    """Under the default straggler victim a generating row is untouchable —
    unless ``gen_slo`` is set and the row has already missed its per-token
    SLO: then the waiting load evicts it, and the resumed stream is still
    token-exact."""
    u, first = gfix["u"], gfix["first"]
    spec = GenerationSpec(10, first, gen_slo_s=1e-3)  # 0.05 step: all miss
    mk = lambda policy: ContinuousScheduler(  # noqa: E731
        gfix["eng"], rows=1, contention=IDEAL, gen_step_s=0.05,
        preemption=policy,
    ).run(_requests(
        gfix,
        [BandwidthTrace.constant(3 * u), BandwidthTrace.constant(50 * u)],
        sess_kw=[dict(fixed_level=0), dict(fixed_level=0)],
        arrivals=[0.0, 0.55],
        specs=[spec, None],
    ))

    keep = mk(PreemptionPolicy())  # straggler, gen_slo off: no candidates
    assert keep.n_preemptions == 0

    out = mk(PreemptionPolicy(gen_slo=True))
    t0 = out.timeline
    assert out.n_preemptions >= 1 and out.n_resumes >= 1
    assert t0[0].preempt_ts[0] > t0[0].finish_t  # evicted mid-generation
    assert t0[0].gen_slo_miss == 10  # every token's TPOT over the 1 ms SLO
    want = _oracle_tokens(gfix, out.sessions[0].caches, first, 10)
    assert t0[0].tokens_out == want  # bit-exact continuation
    assert out.sessions[1].ttft_s < 1.25  # the waiter met its SLO
