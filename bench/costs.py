"""Operations and bytes the served work needs, computed from shapes.

These are the least a device could do for the work: what a roofline share
divides by the measured device time.  ``cfg`` is a configuration file's
dict (``bench/configs/<name>.json``); what depends on its architecture is
counted by the reference module that its ``reference`` key names
(``bench/reference/__init__.py``).
"""
from __future__ import annotations

from typing import Iterable

from bench import reference

BF16 = 2
F32 = 4
U16 = 2


def decode_step(cfg: dict, lengths: Iterable[int]) -> dict:
    """One decode step for the active rows, each holding ``length`` tokens
    before the step: the least FLOPs and HBM bytes."""
    return reference.load(cfg["reference"]).decode_step(cfg, lengths)


def token_kernels(cfg: dict, codec: dict, chunk_tokens: int,
                  n_lossless: int, n_lossy: int) -> dict:
    """The fused token kernels of ``kernels/kvquant.py`` over decoded chunks.

    Lossless (level 0): reads uint16 delta and anchor symbols and one f32
    scale per group, writes bf16 tokens of whole groups; 2 FLOPs per token
    element (anchor plus delta, times the scale).  Lossy: reads uint16 delta
    symbols, f32 anchors and one f32 bin per row, writes bf16 tokens; 3
    FLOPs per delta element (centre, scale, add the anchor).
    """
    rows, C = reference.load(cfg["reference"]).token_block(cfg)
    g = codec["group_size"]
    G = -(-chunk_tokens // g)
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
