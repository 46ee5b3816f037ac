"""Operations and bytes the served work needs, computed from shapes.

These are the least a device could do for the work: what a roofline share
divides by the measured device time.  ``cfg`` is a configuration file's
dict (``bench/configs/<name>.json``).
"""
from __future__ import annotations

from typing import Iterable

BF16 = 2
F32 = 4
U16 = 2


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    return dict(
        L=cfg["num_hidden_layers"], d=d, hq=hq, hkv=cfg["num_key_value_heads"],
        dh=cfg.get("head_dim", d // hq), ff=cfg["intermediate_size"],
        V=cfg["vocab_size"],
    )


def n_params(cfg: dict) -> int:
    """Weights of the served model (tied embedding counted once; norm gains
    included)."""
    m = dims(cfg)
    per_layer = (
        m["d"] * m["hq"] * m["dh"] * 2  # wq, wo
        + m["d"] * m["hkv"] * m["dh"] * 2  # wk, wv
        + 3 * m["d"] * m["ff"]  # gate, up, down
    )
    gains = (2 * m["L"] + 1) * m["d"] if cfg["norm"] == "rmsnorm" else 0
    return m["L"] * per_layer + m["V"] * m["d"] + gains


def kv_bytes_per_token(cfg: dict) -> int:
    """bf16 K and V of one token over all layers."""
    m = dims(cfg)
    return m["L"] * 2 * m["hkv"] * m["dh"] * BF16


def decode_step(cfg: dict, lengths: Iterable[int]) -> dict:
    """One decode step for the active rows, each holding ``length`` tokens
    before the step: the least FLOPs and HBM bytes.

    FLOPs: 2 per weight per row for the layer matmuls and the output head,
    plus 2 x 2 per (query head, head dim, attended position) per layer.
    Bytes: every weight read once, each active row's cached K and V read
    once, its new token's K and V written once.
    """
    m = dims(cfg)
    lengths = [int(n) for n in lengths]
    rows = len(lengths)
    matmul = n_params(cfg) - ((2 * m["L"] + 1) * m["d"] if cfg["norm"] == "rmsnorm" else 0)
    attended = sum(n + 1 for n in lengths)
    flops = 2 * matmul * rows + 4 * m["hq"] * m["dh"] * attended * m["L"]
    per_tok = kv_bytes_per_token(cfg)
    nbytes = n_params(cfg) * BF16 + per_tok * sum(lengths) + per_tok * rows
    return dict(flops=float(flops), bytes=float(nbytes))


def _token_block(cfg: dict, codec: dict, chunk_tokens: int):
    m = dims(cfg)
    g = codec["group_size"]
    groups = -(-chunk_tokens // g)
    rows = m["L"] * 2  # (layer, K/V) rows of one chunk
    channels = m["hkv"] * m["dh"]
    return rows, groups, g, channels


def token_kernels(cfg: dict, codec: dict, chunk_tokens: int,
                  n_lossless: int, n_lossy: int) -> dict:
    """The fused token kernels of ``kernels/kvquant.py`` over decoded chunks.

    Lossless (level 0): reads uint16 delta and anchor symbols and one f32
    scale per group, writes bf16 tokens of whole groups; 2 FLOPs per token
    element (anchor plus delta, times the scale).  Lossy: reads uint16 delta
    symbols, f32 anchors and one f32 bin per row, writes bf16 tokens; 3
    FLOPs per delta element (centre, scale, add the anchor).
    """
    rows, G, g, C = _token_block(cfg, codec, chunk_tokens)
    out = rows * G * g * C * BF16
    deltas = rows * G * (g - 1) * C
    ll_bytes = deltas * U16 + rows * G * C * U16 + rows * G * F32 + out
    lossy_bytes = deltas * U16 + rows * G * C * F32 + rows * F32 + out
    ll_flops = 2 * rows * G * g * C
    lossy_flops = 3 * deltas
    return dict(
        flops=float(n_lossless * ll_flops + n_lossy * lossy_flops),
        bytes=float(n_lossless * ll_bytes + n_lossy * lossy_bytes),
    )


def least_seconds(work: dict, peak: dict) -> float:
    """Roofline time of ``work``: the larger of its compute and memory
    bounds at the device's peaks."""
    return max(work["flops"] / peak["flops_bf16"], work["bytes"] / peak["hbm_bytes_per_s"])
