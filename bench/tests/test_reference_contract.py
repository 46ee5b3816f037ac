"""Everything architecture-specific comes from the module that the
configuration's ``reference`` key names: the weights, the check, the
program's architecture fields and the cost counts.  Runs of the tiny cell
(``tiny.py``) on the CPU, with the cell's own limits."""
import contextlib
import hashlib
import importlib.util
import io
import json
import os
import sys
import types

import pytest

from bench import run, trace_reduce
from bench.cell import Record, load_json
from bench.reference import dense_lm
from bench.tests.tiny import WORKLOAD, tiny_cell

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ARGS = ["--workload", WORKLOAD, "--seconds", "2", "--trace", "0"]
CONTRACT = ("make_key", "init_params", "served_gaps", "program_fields",
            "decode_step", "token_block")
# the four metrics that go through bench/costs.py, read from the Record of
# ``_record`` at the commit before the counts moved into the reference
PINNED = {
    "decode_step.mfu": 0.05825833038632404,
    "decode_step_roofline": 12.93562778785787,
    "kvquant_roofline": 30.96376167697725,
    "load.mfu": 2.5769721991312768e-05,
}


def _run(seed, factory):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(ARGS + ["--seed", str(seed)], require_tpu=False, cell_factory=factory)
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.reduce(os.path.join(HERE, "data", "decode_and_steps.xplane.pb"))


def _record(trace, **cfg):
    """smollm-360m's configuration over the committed trace: one lossless
    and one lossy 256-token chunk, two decode steps of one row."""
    cfg = dict(load_json("configs", "smollm-360m.json"), **cfg)
    rec = Record(cfg=cfg, codec=dict(cfg["codec"], chunk_tokens=256),
                 device_kind="TPU v5 lite", chunk_tokens=256, chunks_lossless=1,
                 chunks_lossy=1, step_lengths=[[768], [769]])
    rec.trace = trace
    return rec


def _read(name, rec):
    spec = importlib.util.spec_from_file_location(f"metric_{name}",
                                                  os.path.join(BENCH, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)


def _digest():
    paths = [os.path.join(BENCH, p) for p in ("cell.py", "costs.py")] + sorted(
        os.path.join(BENCH, "metrics", f"{m}.py") for m in PINNED)
    return {p: hashlib.sha256(open(p, "rb").read()).hexdigest() for p in paths}


@pytest.mark.parametrize("metric", sorted(PINNED))
def test_cost_metrics_read_as_before(trace, metric):
    assert _read(metric, _record(trace)) == PINNED[metric]


def test_a_second_reference_drives_the_cell(monkeypatch, trace):
    """A reference registered under another name, delegating to
    ``dense_lm``, makes the weights, checks the served tokens and counts
    the costs: no file of the harness names it."""
    calls = []
    mod = types.ModuleType("bench.reference.recorded_lm")
    mod.F32 = dense_lm.F32
    for name in CONTRACT:
        def call(*a, _name=name, **k):
            calls.append(_name)
            return getattr(dense_lm, _name)(*a, **k)
        setattr(mod, name, call)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    before = _digest()

    got = _run(21, tiny_cell(config={"reference": "recorded_lm"}))
    assert got["correct"] is True, got["checks"]
    assert {"make_key", "init_params", "program_fields", "served_gaps"} <= set(calls)

    calls.clear()
    rec = _record(trace, reference="recorded_lm")
    assert {m: _read(m, rec) for m in PINNED} == PINNED
    assert set(calls) == {"decode_step", "token_block"}
    assert _digest() == before


def test_unknown_reference_exits_before_weights(monkeypatch):
    made = []
    monkeypatch.setattr(dense_lm, "init_params", lambda *a, **k: made.append(a))
    with pytest.raises(SystemExit) as e:
        _run(22, tiny_cell(config={"reference": "no_such_lm"}))
    assert "bench.reference.no_such_lm" in str(e.value)
    assert os.path.join("bench", "configs", "smollm-360m.json") in str(e.value)
    assert made == []


def test_program_fields_reach_the_engine(monkeypatch):
    """Two of the tiny model's four layers held here, as a chip's share: the
    cut is written once, in the configuration's own key, and the engine and
    the reference both serve two layers."""
    from repro.serving import engine

    archs = []
    init = engine.Engine.__init__

    def record(self, cfg, *a, **k):
        archs.append(cfg)
        init(self, cfg, *a, **k)

    monkeypatch.setattr(engine.Engine, "__init__", record)
    got = _run(23, tiny_cell(config={"num_hidden_layers": 2}))
    assert got["correct"] is True, got["checks"]
    assert {a.n_layers for a in archs} == {2}


def test_a_field_the_program_lacks_exits_before_weights(monkeypatch):
    """A reference whose configuration implies a field that the program's
    architecture does not have fails, naming the field, before any weights."""
    mod = types.ModuleType("bench.reference.windowed_lm")
    for name in CONTRACT:
        setattr(mod, name, getattr(dense_lm, name))
    mod.F32 = dense_lm.F32
    mod.program_fields = lambda cfg: dict(dense_lm.program_fields(cfg), window_size=2048)
    made = []
    mod.init_params = lambda *a, **k: made.append(a)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    with pytest.raises(TypeError, match="window_size"):
        _run(24, tiny_cell(config={"reference": "windowed_lm"}))
    assert made == []
