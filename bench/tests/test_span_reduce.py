"""The program-span reduction (``bench/spans.py``) and the four metrics that
read it.

``data/serve_spans.xplane.pb`` (``record_span_trace.py``): smollm-360m at
published widths on one TPU v5e; inside a ``bench.wave`` span, one
``ContinuousScheduler.run`` of one request that fetches and decodes one
256-token chunk at level 0 and generates two tokens, with the program's
spans and the benchmark's ``bench.*`` spans.  The expected numbers were read
off the trace by hand: the host plane's span events and the
``/device:TPU:0`` plane's operation events.
"""
import importlib.util
import os

import pytest

from bench import spans, trace_reduce
from bench.cell import Record

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "data", "serve_spans.xplane.pb")
METRICS = os.path.join(os.path.dirname(HERE), "metrics")


def metric(name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(METRICS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def record(table=None, lossless=10, lossy=2, trace=True):
    rec = Record(cfg={}, codec={}, device_kind="TPU v5 lite", chunk_tokens=256,
                 chunks_lossless=lossless, chunks_lossy=lossy)
    if trace:
        rec.trace = {"busy_s": 1.0, "window_s": 2.0}
        if table is not None:
            rec.trace["spans"] = table
    return rec


def row(n, s, self_s=None):
    return dict(n=n, s=s, self_s=s if self_s is None else self_s)


TABLE = {
    "sched.round": row(4, 0.100, 0.010),
    "stream.fetch_wait": row(24, 0.018),
    "codec.parse": row(3, 0.042),
    "sched.gen_step": row(50, 1.000, 0.025),
    "sched.logits_sync": row(50, 0.050),
}


def test_fetch_wait_per_chunk():
    read = metric("stream.fetch_wait_ms_per_chunk")
    assert read(record(TABLE)) == pytest.approx(18.0 / 12)
    assert read(record(TABLE, 0, 0)) is None


def test_host_parse_per_chunk():
    assert metric("codec.host_parse_ms_per_chunk")(record(TABLE)) == pytest.approx(42.0 / 12)


def test_host_per_round():
    # self time of admission, the rounds, the steps and Algorithm 1: the
    # spans nested in them (fetch waits, parses, codec, insert and
    # completion calls) left out
    read = metric("sched.host_ms_per_round")
    table = dict(TABLE, **{"sched.admit": row(3, 0.003), "stream.step": row(12, 0.030, 0.012),
                           "stream.decide": row(12, 0.001), "sched.complete": row(4, 0.5)})
    assert read(record(table)) == pytest.approx((3.0 + 10.0 + 12.0 + 1.0) / 4)
    assert read(record({"sched.round": row(2, 0.010)})) == pytest.approx(5.0)


def test_host_per_step():
    # the step's self time: the nested logits sync (and the benchmark's
    # probe around the engine call) left out
    assert metric("gen.host_ms_per_step")(record(TABLE)) == pytest.approx(25.0 / 50)


@pytest.mark.parametrize("name", ["stream.fetch_wait_ms_per_chunk",
                                  "codec.host_parse_ms_per_chunk",
                                  "sched.host_ms_per_round", "gen.host_ms_per_step"])
def test_silent_without_program_spans(name, tmp_path, monkeypatch):
    read = metric(name)
    assert read(record(trace=False)) is None
    # a program without spans: the table holds only the benchmark's own
    assert read(record({"bench.wave": row(1, 1.0)})) is None
    # a trace summary with no trace file beside it
    monkeypatch.setattr(spans, "TRACE_DIR", str(tmp_path))
    assert read(record()) is None


def test_span_table_self_time():
    line = [(0, 100, "a"), (10, 30, "b"), (12, 20, "c"), (40, 60, "b"), (100, 120, "d")]
    got = spans.span_table([line])
    assert got["a"] == dict(n=1, s=pytest.approx(100e-9), self_s=pytest.approx(60e-9))
    assert got["b"] == dict(n=2, s=pytest.approx(40e-9), self_s=pytest.approx(32e-9))
    assert got["c"]["self_s"] == pytest.approx(8e-9)
    assert got["d"]["self_s"] == pytest.approx(20e-9)


def test_gaps_named_by_innermost_span():
    lines = [[(0, 100, "a"), (10, 30, "b"), (12, 20, "c")], [(5, 95, "x")]]
    got = spans.name_gaps(lines, [(13, 15), (31, 33), (0, 4), (96, 98), (200, 210)])
    assert got == pytest.approx({"c": 2e-9, "x": 2e-9, "a": 6e-9, spans.OUTSIDE: 10e-9})


def test_pr12_fixture_names_gaps_as_trace_reduce():
    # with only the benchmark's spans, the sweep names every gap as the
    # scan of ``trace_reduce`` does
    path = os.path.join(HERE, "data", "decode_and_steps.xplane.pb")
    want = trace_reduce.reduce(path)
    got = spans.reduce(path)
    assert got["window_s"] == pytest.approx(want["window_s"], abs=1e-9)
    assert dict(got["idle_gaps"]) == pytest.approx(dict(want["idle_gaps"]), abs=1e-9)


@pytest.fixture(scope="module")
def served():
    return spans.reduce(TRACE)


def test_fixture_spans(served):
    t = served["spans"]
    # the window is the one bench.wave span
    assert served["window_s"] == pytest.approx(0.279741887, abs=1e-9)
    assert t["sched.run"]["n"] == 1 and t["bench.wave"]["n"] == 1
    # round 1 issues the fetch (0.806390 ms), round 2 resolves, decodes and
    # inserts the chunk (26.355760 ms); their self time is what is left of
    # each after stream.step, bench.decode_chunk_runs, bench.insert_runs
    # and sched.complete
    assert t["sched.round"] == dict(n=2, s=pytest.approx(0.02716215, abs=1e-9),
                                    self_s=pytest.approx(0.00031939, abs=1e-9))
    assert t["stream.step"] == dict(n=2, s=pytest.approx(0.01074251, abs=1e-9),
                                    self_s=pytest.approx(0.0031477, abs=1e-9))
    assert t["stream.fetch_wait"]["s"] == pytest.approx(0.00753608, abs=1e-9)
    assert t["codec.parse"]["s"] == pytest.approx(0.00215767, abs=1e-9)
    assert t["codec.dispatch"]["s"] == pytest.approx(0.01166948, abs=1e-9)
    # two steps of 239.105278 and 9.667330 ms, less the probe's engine
    # call and the logits sync nested in each
    assert t["sched.gen_step"] == dict(n=2, s=pytest.approx(0.248772608, abs=1e-9),
                                       self_s=pytest.approx(0.002629932, abs=1e-9))
    assert t["sched.logits_sync"]["s"] == pytest.approx(0.008380029, abs=1e-9)


def test_fixture_gap_named_by_program_span(served):
    gaps = dict(served["idle_gaps"])
    # one gap, 45.826033 to 62.723140 ms into the trace: the device waits
    # from the fresh cache's zeros to the chunk's first rANS operation; its
    # midpoint lies in the fetch wait (48.992530 to 56.528610 ms)
    assert gaps["stream.fetch_wait"] == pytest.approx(0.016897107, abs=1e-9)
    want = trace_reduce.reduce(TRACE)
    idle = want["window_s"] - want["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, abs=1e-9)
    # only the two gaps before sched.run starts (0.683806 and 0.542076 ms)
    # are left to the wave
    assert gaps["bench.wave"] == pytest.approx(0.001225882, abs=1e-9)


def test_fixture_metrics(tmp_path, monkeypatch):
    # the readers find the run's trace where bench/run.py writes it
    run_dir = tmp_path / "plugins" / "profile" / "run"
    run_dir.mkdir(parents=True)
    os.symlink(TRACE, run_dir / "host.xplane.pb")
    monkeypatch.setattr(spans, "TRACE_DIR", str(tmp_path))
    rec = record(lossless=1, lossy=0)
    assert metric("stream.fetch_wait_ms_per_chunk")(rec) == pytest.approx(7.53608, abs=1e-6)
    assert "spans" in rec.trace
    assert metric("codec.host_parse_ms_per_chunk")(rec) == pytest.approx(2.15767, abs=1e-6)
    # self ms of sched.admit 0.10093, sched.round 0.31939, stream.step
    # 3.14770, stream.decide 0.05873, over two rounds
    assert metric("sched.host_ms_per_round")(rec) == \
        pytest.approx((0.10093 + 0.31939 + 3.14770 + 0.05873) / 2, abs=1e-6)
    assert metric("gen.host_ms_per_step")(rec) == pytest.approx(2.629932 / 2, abs=1e-6)
