"""Pallas TPU kernels: fused KV delta-(de)quantization (CacheGen decode hot path).

The paper's serving node spends its codec time in (a) entropy decode and
(b) tensor reconstruction (dequantize deltas, add anchors, cast).  (a) is the
lane-parallel rANS scan (core/rans.py); (b) is a memory-bound elementwise+
broadcast op over the full KV tensor — the natural Pallas kernel.  On TPU the
win is fusing dequant + anchor-broadcast-add + dtype cast into one pass so
the KV tensor is written to HBM exactly once, in the layout the attention
kernel wants.

Layout: the chunk's tokens are *grouped* (group_size g): deltas are
``(G, g-1, C)`` and anchors ``(G, C)``; out[i, j, :] = d[i, j, :] * bin +
anchor[i, :].  Grid = (L2, G/Bg); each block holds Bg whole groups with the
full channel width so the anchor broadcast never crosses blocks.

TPU tiling (token kernels): Mosaic wants a block's last two dimensions to
be multiples of (8, 128) or whole.  The group axis G (26 for 256-token
chunks in groups of 10) is neither, so per-group operands carry singleton
axes that make their last two dimensions whole: anchors ``(B, G, 1, C)``,
per-group scales ``(B, G, 1, 1)``, per-row bins ``(B, 1, 1)``.  uint16
symbols widen through int32 (Mosaic has no uint16 -> f32 cast).
``tests/test_chip_compile.py`` compiles both token kernels for a v5e.

Fused-path / oracle split (PR 1): these kernels are the *production* decode
path — ``core/codec.decode_chunks`` feeds them whole batches of chunks (the
leading axis folds n_chunks × L × 2) and they emit full token blocks
``(·, G, g, C)`` with the anchor in slot 0, so no separate anchor scatter or
merge pass touches HBM afterwards.  The unfused reference ops in
``core/quant.py`` and the pure-jnp twins in ``kernels/ref.py`` are retained
as the correctness oracle; on CPU the kernels run under ``interpret=True``
and are tested against that oracle (tests/test_kernels.py).

Two decode variants mirror the codec's two encoding families:

* :func:`kv_dequant_tokens_pallas` — lossy levels: per-(layer,kv) bin widths,
  f32 anchors already dequantized, out = [anchor; d*bin + anchor].
* :func:`kv_lossless_tokens_pallas` — level 0 ("lossless-after-8bit"):
  integer symbol deltas + per-group shared scales, bit-exact w.r.t. the
  8-bit quantization.

Encode-side fusion (delta + scale + round + clip) is the mirror image and is
provided for the offline ``store_kv`` path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "kv_dequant_pallas",
    "kv_quant_pallas",
    "kv_dequant_tokens_pallas",
    "kv_lossless_tokens_pallas",
    "pick_block_groups",
]


def pick_block_groups(G: int, requested: int) -> int:
    """Largest divisor of ``G`` that is <= ``requested`` (>= 1).

    The grid tiles whole groups; a non-divisible ``G % block_groups`` simply
    shrinks the block instead of raising.
    """
    bg = max(1, min(int(requested), int(G)))
    while G % bg:
        bg -= 1
    return bg


def _dequant_kernel(d_sym_ref, anchors_ref, bins_ref, out_ref, *, qmax: int):
    # d_sym: (1, Bg, gm1, C) uint16 | anchors: (1, Bg, C) f32 | bins: (1, 1) f32
    d = d_sym_ref[0].astype(jnp.float32) - float(qmax)
    b = bins_ref[0, 0]
    anchor = anchors_ref[0][:, None, :]  # (Bg, 1, C)
    out_ref[0] = (d * b + anchor).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("qmax", "block_groups", "out_dtype", "interpret")
)
def kv_dequant_pallas(
    d_sym: jnp.ndarray,  # (L2, G, g-1, C) uint16 delta symbols
    anchors: jnp.ndarray,  # (L2, G, C) f32 dequantized anchors
    bins: jnp.ndarray,  # (L2,) f32 per-(layer,kv) effective bin width
    *,
    qmax: int,
    block_groups: int = 8,
    out_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused (dequant + anchor add + cast): returns (L2, G, g-1, C)."""
    L2, G, gm1, C = d_sym.shape
    Bg = pick_block_groups(G, block_groups)
    grid = (L2, G // Bg)
    return pl.pallas_call(
        functools.partial(_dequant_kernel, qmax=qmax),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Bg, gm1, C), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, Bg, C), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, Bg, gm1, C), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((L2, G, gm1, C), out_dtype),
        interpret=interpret,
    )(d_sym, anchors, bins.reshape(L2, 1).astype(jnp.float32))


def _dequant_tokens_kernel(d_sym_ref, anchors_ref, bins_ref, out_ref, *, qmax: int):
    # d_sym: (1, Bg, g-1, C) | anchors: (1, Bg, 1, C) f32 | bins: (1, 1, 1)
    # out: (1, Bg, g, C)
    d = d_sym_ref[0].astype(jnp.int32).astype(jnp.float32) - float(qmax)
    b = bins_ref[0]  # (1, 1)
    anchor = anchors_ref[0]  # (Bg, 1, C)
    tokens = jnp.concatenate([anchor, d * b + anchor], axis=1)  # (Bg, g, C)
    out_ref[0] = tokens.astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("qmax", "block_groups", "out_dtype", "interpret")
)
def kv_dequant_tokens_pallas(
    d_sym: jnp.ndarray,  # (B, G, g-1, C) uint16 delta symbols
    anchors: jnp.ndarray,  # (B, G, C) f32 dequantized anchors
    bins: jnp.ndarray,  # (B,) f32 effective bin widths
    *,
    qmax: int,
    block_groups: int = 8,
    out_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused lossy decode to *whole token groups*: returns (B, G, g, C).

    Slot 0 of every group is the anchor itself; slots 1..g-1 are
    ``delta * bin + anchor``.  One HBM write produces the final token-major
    KV block — no separate anchor scatter/merge pass.  The leading axis B
    may fold (n_chunks, L, 2) for batched multi-chunk decode.
    """
    B, G, gm1, C = d_sym.shape
    Bg = pick_block_groups(G, block_groups)
    grid = (B, G // Bg)
    return pl.pallas_call(
        functools.partial(_dequant_tokens_kernel, qmax=qmax),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Bg, gm1, C), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, Bg, 1, C), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Bg, gm1 + 1, C), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, G, gm1 + 1, C), out_dtype),
        interpret=interpret,
    )(
        d_sym,
        anchors.reshape(B, G, 1, C),
        bins.reshape(B, 1, 1).astype(jnp.float32),
    )


def _lossless_tokens_kernel(d_sym_ref, a_sym_ref, scales_ref, out_ref):
    # d_sym: (1, Bg, g-1, C) uint16 integer-delta symbols (bias 254)
    # a_sym: (1, Bg, 1, C) uint16 8-bit anchor symbols (bias 128)
    # scales: (1, Bg, 1, 1) f32 per-group shared scale
    q_a = a_sym_ref[0].astype(jnp.int32).astype(jnp.float32) - 128.0  # (Bg, 1, C)
    q_d = d_sym_ref[0].astype(jnp.int32).astype(jnp.float32) - 254.0  # (Bg, g-1, C)
    s = scales_ref[0]  # (Bg, 1, 1)
    anchor = q_a * s  # (Bg, 1, C)
    others = (q_d + q_a) * s  # (Bg, g-1, C)
    tokens = jnp.concatenate([anchor, others], axis=1)
    out_ref[0] = tokens.astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_groups", "out_dtype", "interpret")
)
def kv_lossless_tokens_pallas(
    d_sym: jnp.ndarray,  # (B, G, g-1, C) uint16 integer-delta symbols
    a_sym: jnp.ndarray,  # (B, G, C) uint16 8-bit anchor symbols
    scales: jnp.ndarray,  # (B, G) f32 per-group shared scales
    *,
    block_groups: int = 8,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused level-0 ("lossless-after-8bit") decode to token groups.

    Reconstruction is ``(d - 254 + (a - 128)) * scale`` for delta slots and
    ``(a - 128) * scale`` for the anchor slot — bit-exact (in f32) with the
    unfused ``quant.lossless_reconstruct`` oracle.  Returns (B, G, g, C).
    """
    B, G, gm1, C = d_sym.shape
    Bg = pick_block_groups(G, block_groups)
    grid = (B, G // Bg)
    return pl.pallas_call(
        _lossless_tokens_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Bg, gm1, C), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, Bg, 1, C), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, Bg, 1, 1), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Bg, gm1 + 1, C), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, G, gm1 + 1, C), out_dtype),
        interpret=interpret,
    )(
        d_sym,
        a_sym.reshape(B, G, 1, C),
        scales.reshape(B, G, 1, 1).astype(jnp.float32),
    )


def _quant_kernel(kv_ref, bins_ref, sym_ref, *, qmax: int, gm1: int):
    # kv: (1, Bg, g, C) f32 grouped tokens; out symbols for the g-1 deltas
    kv = kv_ref[0].astype(jnp.float32)  # (Bg, g, C)
    anchor = kv[:, :1, :]
    delta = kv[:, 1:, :] - anchor  # (Bg, g-1, C)
    b = bins_ref[0, 0]
    q = jnp.clip(jnp.round(delta / b), -qmax, qmax) + qmax
    sym_ref[0] = q.astype(jnp.uint16)


@functools.partial(jax.jit, static_argnames=("qmax", "block_groups", "interpret"))
def kv_quant_pallas(
    kv_grouped: jnp.ndarray,  # (L2, G, g, C) f32 tokens grouped by anchor
    bins: jnp.ndarray,  # (L2,) f32
    *,
    qmax: int,
    block_groups: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused (delta + scale + round + clip) encode: returns (L2, G, g-1, C)."""
    L2, G, g, C = kv_grouped.shape
    Bg = pick_block_groups(G, block_groups)
    grid = (L2, G // Bg)
    return pl.pallas_call(
        functools.partial(_quant_kernel, qmax=qmax, gm1=g - 1),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Bg, g, C), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, Bg, g - 1, C), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((L2, G, g - 1, C), jnp.uint16),
        interpret=interpret,
    )(kv_grouped, bins.reshape(L2, 1).astype(jnp.float32))
