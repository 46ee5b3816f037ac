"""The command's contract, on the CPU: refusal without a TPU, refusal
without the system under test, and the schema of the last line."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import run
from bench.tests.tiny import WORKLOAD, tiny_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ARGS = ["--workload", WORKLOAD, "--seed", "3", "--seconds", "2", "--trace", "0"]


def test_refuses_without_a_tpu(capsys):
    assert run.main(ARGS) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", "out", "__pycache__"))
    env = dict(os.environ, PYTHONPATH="")
    p = subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""


@pytest.fixture(scope="module")
def last_line():
    import contextlib
    import io

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(ARGS, require_tpu=False, cell_factory=tiny_cell())
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_last_line_schema(last_line):
    assert list(last_line)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in last_line
    assert last_line["correct"] is True
    assert last_line["attempted"] > 0 and last_line["failed"] == 0
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    e2e = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    assert set(last_line["metrics"]) == set(e2e)
    for name, m in last_line["metrics"].items():
        assert m["unit"] == e2e[name] and m["value"] > 0
    assert set(last_line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    for c in last_line["checks"].values():
        assert set(c) == {"value", "limit"}
