"""Adaptive serving-session scenario matrix (live Algorithm 1, real bitstreams).

Where fig14_slo.py scores Algorithm 1 on *byte counts* through the offline
simulator, this benchmark runs the real closed loop
(``repro.serving.session.ServeSession``): per chunk it measures realized
throughput from the trace-driven fetch, re-plans the streaming configuration,
fetches the actual bitstream from the store, decodes it through the fused
``codec.decode_chunks`` → ``Engine.decode_to_cache`` path (or recomputes
TEXT chunks with ``Engine.prefill_extend``), and finally checks the
materialized cache's logits against the full-prefill reference.

Matrix: {flat, falling, oscillating, straggler} bandwidth traces × 2–3
registry architectures × {adaptive, fixed-level-1 (quant8-style single
representation, no adaptation)}.  Traces are expressed in units of ``u`` =
the bandwidth that streams the whole level-1 context in exactly 1 s, so the
same scenario shapes exercise every architecture regardless of its absolute
bitstream sizes.  GPU recompute is modeled at paper scale relative to the
SLO (a per-scenario fraction of the SLO per chunk, standing in for serving
concurrency/GPU load, Fig. 13a) — tiny CPU models recompute nearly for
free, which would make TEXT trivially dominant and no level adaptation
would ever be observable.  The falling scenario models an idle GPU: the
session streams while bandwidth holds, then rescues the SLO through the
paper's text-recompute fallback once even coarse levels can't fit; the
oscillating scenario models a busy GPU, where rescue must come from level
escalation alone (the realized histogram bounces between fine and coarse).

Per scenario we record: TTFT (virtual clock, simulator-comparable), SLO
verdict, realized-level histogram, total wire bytes, realized host decode
time, and logit drift (max |Δ| + argmax agreement of the next-token logits
vs. the exact-prefill reference).  Results go to ``BENCH_session.json`` at
the repo root (uploaded as a CI artifact); the headline acceptance check —
on the falling trace the adaptive session meets an SLO that the fixed-level
baseline misses — is summarized under ``"acceptance"``.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

BENCH_SESSION_FILENAME = "BENCH_session.json"
_BENCH_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", BENCH_SESSION_FILENAME
)

DEFAULT_ARCHS = ("smollm-360m", "olmo-1b", "qwen2-moe-a2.7b")
LEVEL_MULTS = (0.5, 1.0, 4.0, 16.0)  # widened spread: coarsest ~1.8x smaller than l1
GROUP_SIZE = 24  # fewer level-invariant anchors -> more spread between levels
CHUNK_TOKENS = 32  # 6 chunks per context: enough re-plan points to adapt


@dataclasses.dataclass
class ArchAssets:
    arch: str
    cfg: object
    engine: object
    streamer: object
    tokens: np.ndarray
    ref_logits: np.ndarray  # (B, vocab) full-prefill next-token logits
    u_gbps: float  # bandwidth streaming the level-1 context in 1 s
    level_totals: Dict[int, int]


def build_assets(arch: str, *, ctx_len: int = 192, chunk_tokens: int = CHUNK_TOKENS,
                 precision: int = 10, seed: int = 0) -> ArchAssets:
    import jax
    import jax.numpy as jnp

    from repro.configs import registry
    from repro.core import codec as kvcodec
    from repro.models import build
    from repro.serving.engine import Engine
    from repro.serving.kv_layout import caches_to_codec_kv
    from repro.streaming import CacheGenStreamer, KVStore

    cfg = registry.get(arch).tiny()
    if cfg.family not in ("dense", "moe"):
        raise ValueError(f"{arch}: adaptive-session bench needs text prefill_extend")
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))
    engine = Engine(cfg, params, cache_capacity=ctx_len + 32)
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab_size, size=(1, ctx_len)).astype(np.int32)
    logits, caches = engine.calculate_kv({"tokens": jnp.asarray(tokens)})
    kv = caches_to_codec_kv(caches, 0, ctx_len)
    ctab = kvcodec.profile(
        [kv],
        kvcodec.CodecConfig(
            precision=precision, group_size=GROUP_SIZE, level_mults=LEVEL_MULTS
        ),
    )
    store = KVStore(ctab)
    streamer = CacheGenStreamer(store, cfg)
    metas = store.store_kv("ctx", kv, chunk_tokens=chunk_tokens)
    level_totals = {
        lvl: sum(m.sizes[lvl] for m in metas) for lvl in metas[0].sizes
    }
    u_gbps = level_totals[1] * 8.0 / 1e9  # level-1 context in exactly 1 s
    return ArchAssets(
        arch=arch,
        cfg=cfg,
        engine=engine,
        streamer=streamer,
        tokens=tokens,
        ref_logits=np.asarray(logits[:, -1], np.float32),
        u_gbps=u_gbps,
        level_totals=level_totals,
    )


def scenario_matrix(u: float) -> Dict[str, dict]:
    """Trace shapes in units of u (bandwidth: level-1 context in 1 s).

    ``recompute_frac`` is the modeled GPU recompute cost of one chunk as a
    fraction of the scenario SLO (low = idle GPU, TEXT fallback viable;
    high = busy GPU, only level escalation can rescue the SLO).
    """
    from repro.streaming import BandwidthTrace

    return {
        # comfortable headroom: the session should settle at fine levels
        "flat": dict(
            trace=BandwidthTrace.constant(2.0 * u),
            slo_s=1.0,
            recompute_frac=0.45,
            net_kwargs={},
        ),
        # decent start, ~2x fall mid-stream; GPU idle: after the first
        # streamed chunk the session sees the fall coming and rescues the
        # SLO via the paper's text-recompute fallback — the fixed level
        # keeps streaming and misses
        "falling": dict(
            trace=BandwidthTrace.steps(0.2, [1.0 * u, 0.55 * u]),
            slo_s=1.25,
            recompute_frac=0.15,
            net_kwargs={},
        ),
        # bandwidth bounces, GPU busy (TEXT never viable): the per-chunk
        # throughput estimate chases the link; at an SLO both modes can
        # meet, the adaptive win is *quality* — it realizes finer levels
        # (lower logit drift) than the fixed medium level
        "oscillating": dict(
            trace=BandwidthTrace.steps(
                0.15, [2.0 * u, 0.4 * u, 2.0 * u, 0.4 * u, 2.0 * u, 0.4 * u]
            ),
            slo_s=1.7,
            recompute_frac=0.45,
            net_kwargs={},
        ),
        # flat link with a heavy straggler tail (hedged duplicated fetches
        # with real cancellation are scored in benchmarks/transport_session.py)
        "straggler": dict(
            trace=BandwidthTrace.constant(2.0 * u),
            slo_s=1.5,
            recompute_frac=0.45,
            net_kwargs=dict(straggler_p=0.3, straggler_scale_s=0.25,
                            straggler_alpha=1.5),
        ),
    }


def _logit_drift(assets: ArchAssets, caches) -> Tuple[float, float, bool]:
    """Next-token logits from the materialized cache vs. exact prefill."""
    import jax.numpy as jnp

    eng = assets.engine
    caches_m = caches._replace(length=caches.length - 1)
    logits, _ = eng._decode(
        eng.params, jnp.asarray(assets.tokens[:, -1:], jnp.int32), caches_m
    )
    got = np.asarray(logits[:, -1], np.float32)
    d = np.abs(got - assets.ref_logits)
    return (
        float(d.max()),
        float(d.mean()),
        bool(np.argmax(got) == np.argmax(assets.ref_logits)),
    )


def run(
    archs=DEFAULT_ARCHS,
    *,
    out_path: Optional[str] = _BENCH_PATH,
    seed: int = 0,
    verbose: bool = True,
) -> dict:
    import jax

    from repro.serving.session import ServeSession
    from repro.streaming import NetworkModel
    from repro.streaming.adaptation import TEXT

    scenarios: List[dict] = []
    acceptance: Dict[str, bool] = {}
    for arch in archs:
        assets = build_assets(arch, seed=seed)
        for name, sc in scenario_matrix(assets.u_gbps).items():
            slo = sc["slo_s"]
            # modeled GPU seconds to recompute one chunk (paper regime:
            # recompute is expensive relative to the SLO; see module doc)
            recompute_s = (
                lambda t, p, _s=slo, _f=sc["recompute_frac"]:
                _f * _s * t / CHUNK_TOKENS
            )
            for mode in ("adaptive", "fixed"):
                session = ServeSession(
                    assets.streamer,
                    assets.engine,
                    slo_s=slo,
                    recompute_s=recompute_s,
                    fixed_level=None if mode == "adaptive" else 1,
                    # double-buffer: two chunks per decode run
                    max_run_tokens=2 * CHUNK_TOKENS,
                )
                net = NetworkModel(sc["trace"], seed=seed + 17, **sc["net_kwargs"])
                # no prior bandwidth knowledge: chunk 0 streams at the
                # default medium level (paper §5.3)
                res = session.run("ctx", assets.tokens, net)
                drift_max, drift_mean, agree = _logit_drift(assets, res.caches)
                row = {
                    "arch": arch,
                    "trace": name,
                    "mode": mode,
                    "slo_s": slo,
                    "ttft_s": res.ttft_s,
                    "slo_ok": not res.slo_violated,
                    "levels": {str(k): v for k, v in sorted(res.level_histogram().items())},
                    "total_bytes": res.total_bytes,
                    "n_runs": res.n_runs,
                    "wall_total_s": res.wall_total_s,
                    "logit_drift_max": drift_max,
                    "logit_drift_mean": drift_mean,
                    "argmax_agree": agree,
                    "n_text_chunks": sum(1 for c in res.configs if c == TEXT),
                }
                scenarios.append(row)
                if verbose:
                    print(
                        f"[{arch:>18s} {name:>11s} {mode:>8s}] "
                        f"ttft={res.ttft_s:.3f}s ok={row['slo_ok']} "
                        f"levels={row['levels']} drift={drift_max:.3g}"
                    )
        ok_adapt = next(
            r for r in scenarios
            if r["arch"] == arch and r["trace"] == "falling" and r["mode"] == "adaptive"
        )["slo_ok"]
        ok_fixed = next(
            r for r in scenarios
            if r["arch"] == arch and r["trace"] == "falling" and r["mode"] == "fixed"
        )["slo_ok"]
        acceptance[arch] = bool(ok_adapt and not ok_fixed)

    report = {
        "host_backend": jax.default_backend(),
        "level_mults": list(LEVEL_MULTS),
        "scenarios": scenarios,
        "acceptance": {
            "falling_adaptive_meets_slo_fixed_misses": acceptance,
            "all_archs": bool(all(acceptance.values())),
        },
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=2)
        if verbose:
            print(f"wrote {os.path.abspath(out_path)}")
    return report


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--archs", nargs="*", default=list(DEFAULT_ARCHS))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    rep = run(tuple(args.archs), seed=args.seed)
    print("acceptance:", rep["acceptance"])
