"""``correct`` comes out false when the timed path is broken underneath,
and when the program serves in a lower precision.

Each case skips the look for a chip and drives the rest of a run of the
tiny cell (``tiny.py``) with the cell's own limit."""
import contextlib
import io
import json

import jax.numpy as jnp
import pytest

from bench import run
from bench.tests.tiny import WORKLOAD, tiny_cell

ARGS = ["--workload", WORKLOAD, "--seconds", "2", "--trace", "0"]


def _run(seed, factory=None):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(ARGS + ["--seed", str(seed)], require_tpu=False,
                      cell_factory=factory or tiny_cell())
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_sound_run_is_correct():
    assert _run(11)["correct"] is True


def _altered_token(orig):
    """Each active row's token is replaced, where it is produced, by the
    one the model ranks last."""
    def step(self, tokens, caches, active):
        logits, caches = orig(self, tokens, caches, active)
        worst = jnp.argmin(logits, axis=-1, keepdims=True)
        hot = jnp.arange(logits.shape[-1]) == worst
        return jnp.where(hot, jnp.max(logits) + 1.0, logits), caches
    return step


def _state_unchanged(orig):
    """The step returns the cache it was given: no token's K and V is ever
    written and no row's length advances."""
    def step(self, tokens, caches, active):
        logits, _ = orig(self, tokens, caches, active)
        return logits, caches
    return step


def _chunk_altered(orig):
    """Every decoded run reaches the cache with its K and V reversed along
    the token axis: right bytes, wrong positions."""
    def decode(runs, *a, **k):
        kv, spans = orig(runs, *a, **k)
        return kv[:, :, ::-1], spans
    return decode


@pytest.mark.parametrize("fault", ["altered_token", "state_unchanged", "chunk_altered"])
def test_fault_is_not_correct(monkeypatch, fault):
    from repro.core import codec
    from repro.serving.engine import Engine

    if fault == "chunk_altered":
        monkeypatch.setattr(codec, "decode_chunk_runs", _chunk_altered(codec.decode_chunk_runs))
    else:
        make = {"altered_token": _altered_token, "state_unchanged": _state_unchanged}[fault]
        monkeypatch.setattr(Engine, "decode_step_rows", make(Engine.decode_step_rows))
    got = _run(12)
    assert got["correct"] is False, got["checks"]


def test_control_is_not_correct():
    """The program serving from its weights rounded to float8, the control
    put in its place, fails the cell's own limit through the run's check."""
    got = _run(13, tiny_cell(control=True))
    assert got["correct"] is False, got["checks"]
    assert got["checks"]["logit_gap_share"]["value"] > got["checks"]["logit_gap_share"]["limit"]


def test_reference_control_is_not_correct():
    """The control itself, the reference with float8 weights and float8
    matmul operands, put in the program's place: at each position of the
    served prompts and tokens it puts its own first token, and the run's
    comparison calls those readings not correct."""
    from bench.cell import Cell
    from bench.tests.tiny import TINY

    cell = Cell(WORKLOAD, 14, lambda m: None, overrides=TINY)
    cell.setup()
    cell.window(2.0, None)
    cell.free_program()
    got = cell.check()
    sound, _ = run.judge(got, 0, cell.cell["limits"])
    control = dict(got, gap=got["control_gap"], gap_share=got["control_share"])
    correct, checks = run.judge(control, 0, cell.cell["limits"])
    assert sound is True
    assert correct is False, checks
