"""CPU rehearsal of ``chip_smoke.py``.

The serving phase runs here with the tiny smollm config and the Pallas
kernels in interpret mode (the test, not the script, steers both), and must
drive level-0, lossy and TEXT chunks with every check passing.  ``main()``
itself refuses to run without a TPU.
"""
import chip_smoke
from repro.configs import registry
from repro.core import codec as kvcodec
from repro.streaming.adaptation import TEXT


def test_serving_phase_tiny_runs_every_chunk_kind(monkeypatch):
    # the served decode path picks Pallas by platform; force the kernels
    # (interpreted on CPU) so the scheduler's decodes run them too
    monkeypatch.setattr(kvcodec, "_use_pallas_default", lambda: True)
    out = chip_smoke.serving_phase(
        registry.get("smollm-360m-tiny"),
        ctx_len=120, chunk_tokens=20, capacity=160, gen_tokens=4, seed=0,
    )
    flat = [c for cs in out["configs"] for c in cs]
    assert 0 in flat and TEXT in flat and any(c > 0 for c in flat)
    assert out["configs"][0] == [0] * 6
    checks = out["checks"]
    assert "lossless kernel equals kv_lossless_tokens_ref" in checks
    assert "dequant kernel equals kv_dequant_tokens_ref" in checks
    assert "req 0 level-0 cache equals the reference bit for bit" in checks
    for i in range(4):
        assert f"req {i} scheduler tokens equal generate_with_kv" in checks
        assert f"req {i} decode logits finite" in checks
    assert "no request failed" in checks
    assert "cache rows recycled between requests" in checks


def test_main_refuses_a_cpu_platform(capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert "'cpu'" in captured.err
    assert '"ok"' not in captured.out
