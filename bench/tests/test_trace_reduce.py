"""The trace reduction against a small trace recorded on one TPU v5e.

``data/decode_and_steps.xplane.pb``: smollm-360m at published widths; inside
a ``bench.wave`` span, one ``decode_chunk_runs`` of two 256-token chunks
(level 0 and level 2), one ``insert_runs`` into a 4-row cache, and two
``decode_step_rows`` of one active row, each in its own ``bench.*`` span.
The expected numbers were read off the trace by hand: the module and
operation events of the ``/device:TPU:0`` plane and the host spans.
"""
import os

import pytest

from bench import trace_reduce

TRACE = os.path.join(os.path.dirname(__file__), "data", "decode_and_steps.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce(TRACE)


def test_programs(summary):
    p = summary["programs"]
    # two rANS scans: deltas 438.629 ms, anchors 39.104 ms
    assert p["jit__decode_impl"] == pytest.approx(0.477733, abs=1e-6)
    assert p["jit_insert_codec_runs"] == pytest.approx(0.014593, abs=1e-6)
    assert p["jit__decode_rows_impl"] == pytest.approx(0.014256, abs=1e-6)
    assert p["jit__assemble_chunks"] == pytest.approx(0.000650, abs=1e-6)
    assert summary["top_programs"][0][0] == "jit__decode_impl"


def test_kernels(summary):
    ops = summary["ops"]
    assert ops["kv_lossless_tokens_pallas"] == pytest.approx(87.321e-6, rel=1e-6)
    assert ops["kv_dequant_tokens_pallas"] == pytest.approx(84.885e-6, rel=1e-6)
    assert trace_reduce.seconds_matching(ops, r"^kv_(lossless|dequant)_tokens_pallas$") == \
        pytest.approx(172.206e-6, rel=1e-6)


def test_busy_and_window(summary):
    # the window is the bench.wave span; the device ran most of it
    assert summary["n_devices"] == 1
    assert summary["window_s"] == pytest.approx(0.5200377, abs=1e-7)
    assert summary["busy_s"] == pytest.approx(0.507228, abs=1e-6)
    idle = summary["window_s"] - summary["busy_s"]
    assert sum(v for _, v in summary["idle_gaps"]) == pytest.approx(idle, abs=1e-6)
    assert summary["idle_gaps"][0][0] == "bench.decode_chunk_runs"
