"""Pallas TPU kernel: gather-free lane-parallel rANS decode.

``core/rans.py``'s decode is a ``lax.scan`` whose every step gathers, per
lane, from tables of hundreds of MB (``slot2sym``, ``freqs``, ``cums``) and
from the word buffer.  On a TPU those gathers are random HBM reads with no
reuse, and they hold the decode's time.  This kernel removes them:

* **Grid.**  Blocks of 1024 lanes, laid out as one int32 vreg ``(8, 128)``,
  by tiles of at most ``SYM_TILE`` symbols.  The block's coder state ``x``
  and word pointer ``ptr`` stay in registers through a tile and in the
  resident output blocks between tiles; the block's cumulative-frequency
  rows ``(A', 8, 128)`` and words ``(cap / 2, 8, 128)`` sit in VMEM, read
  from HBM once per block.  So VMEM holds one tile of symbols whatever the
  chunk length, and the rows and words of one block (:func:`vmem_bytes`).
* **Rows laid out once.**  :func:`lane_rows` lays cumulative-frequency
  rows out lane-major.  ``rans`` lays a decode stack's tables out so once,
  and a block whose 1024 lanes read 1024 consecutive tables, aligned, reads
  its rows straight from that layout: ``blocks[b]`` names the row block of
  lane block ``b`` (scalar-prefetched into its index map).  Other lanes get
  their rows gathered into the same layout per call.
* **Symbol lookup by compare-count.**  With ``slot = x mod M``, the symbol
  is the count of ``cums[a] <= slot`` over ``a >= 1``; since a row is
  nondecreasing the compare is monotone in ``a``, so one pass over the row
  finds entry ``s`` (the last that compares true) and entry ``s + 1``.  Each
  entry is packed as ``(cums[a] << 10) | a`` and compared with
  ``(slot << 10) | 1023``, so entry ``s`` carries both ``s`` and
  ``c = cums[s]``: one compare and two selects per entry.  Entries past the
  alphabet hold ``M`` and never compare true.
* **Word fetch by one-hot select** over the block's words, two 16-bit words
  to an int32.
* **Arithmetic** is the scan's, bit for bit, on int32: the state is uint32
  in ``[2^16, 2^32)``, so its shifts are logical, its renormalization test
  is ``x >> 16 == 0``, and products wrap as uint32 products do.

The output equals the scan's (symbols, final state, final pointer), also on
corrupt streams: an exhausted pointer reads word 0, as the scan's clamp
does.  ``tests/test_rans_kernel.py`` checks that in interpret mode;
``tests/test_chip_compile.py`` compiles the kernel for a v5e at
smollm-360m widths, up to chunks of the model's whole context.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rans_decode_pallas", "lane_rows", "vmem_bytes", "BLOCK_LANES", "SYM_TILE"]

_SUB, _LANE = 8, 128
BLOCK_LANES = _SUB * _LANE  # lanes of one grid step: one int32 vreg
SYM_TILE = 128  # symbols per grid step: the symbol block VMEM holds
_UNROLL = 8  # alphabet entries and word pairs per loop iteration
_IDX_BITS = 10  # a row entry is (cums[a] << 10) | a: one compare, two selects
_IDX_MASK = (1 << _IDX_BITS) - 1
_VMEM_MAX = 48 << 20  # refuse beyond this: a v7x TensorCore has 64 MiB
_VMEM_SLACK = 4 << 20  # Mosaic's own scratch above the blocks


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def lane_rows(cums: jnp.ndarray, precision: int) -> jnp.ndarray:
    """Lay rows of cumulative frequencies ``(n, A + 1)`` out as the kernel
    reads them: ``(A', ceil(n / 1024) * 8, 128)`` int32, row ``r``'s entry
    ``a`` at ``[a, r // 128, r % 128]``, packed as ``(cums[a] << 10) | a``;
    entries and rows past the input hold ``M << 10``, which no slot reaches."""
    n, width = cums.shape
    if width > 1 << _IDX_BITS or precision > 14:
        raise ValueError(f"alphabet {width - 1} or precision {precision} too large")
    n_entries = _round_up(width, _UNROLL)
    lanes = _round_up(max(n, 1), BLOCK_LANES)
    entry = jnp.arange(width, dtype=jnp.int32)
    packed = lax.shift_left(cums.astype(jnp.int32), _IDX_BITS) | entry
    packed = jnp.pad(
        packed, ((0, lanes - n), (0, n_entries - width)),
        constant_values=(1 << precision) << _IDX_BITS,
    )
    return packed.T.reshape(n_entries, lanes // _LANE, _LANE)


def vmem_bytes(n_entries: int, cap: int, n_sym: int) -> int:
    """VMEM the kernel's blocks take, double-buffered: one block's rows
    (``n_entries``) and word pairs (``cap`` words), one tile of symbols,
    and the state and pointer blocks in and out."""
    tile = min(n_sym, SYM_TILE)
    pairs = _round_up(max(cap, 1), 2 * _UNROLL) // 2
    return 2 * BLOCK_LANES * 4 * (n_entries + pairs + tile + 4)


def _rans_decode_kernel(
    blocks_ref,  # (n_blocks,) int32 in SMEM: the row block of each lane block
    rows_ref,  # (A', 8, 128) int32: each lane's packed cumulative row
    words_ref,  # (cap / 2, 8, 128) int32: each lane's words, two to an int32
    state_ref,  # (8, 128) int32: initial state (uint32 bits)
    ptr_ref,  # (8, 128) int32: index of the last valid word
    syms_ref,  # (tile, 8, 128) int32 out: this tile's symbols
    x_ref,  # (8, 128) int32 out: the state, carried across tiles
    p_ref,  # (8, 128) int32 out: the pointer, carried across tiles
    *,
    precision: int,
    n_sym: int,
    tile: int,
):
    del blocks_ref  # read by the index maps only
    n_entries = rows_ref.shape[0]
    n_pairs = words_ref.shape[0]
    mask = (1 << precision) - 1
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        x_ref[...] = state_ref[...]
        p_ref[...] = ptr_ref[...]

    def lookup(slot):
        key = lax.shift_left(slot, _IDX_BITS) | _IDX_MASK

        def entries(e, acc):
            # Mosaic carries no mask across loop iterations: carry the last
            # entry read and compare it again
            lo, hi, v = acc
            le = v <= key
            for u in range(_UNROLL):
                v = rows_ref[e * _UNROLL + u]
                hi = jnp.where(le, v, hi)  # entry s + 1
                le = v <= key
                lo = jnp.where(le, v, lo)  # entry s
            return lo, hi, v

        zero = jnp.zeros_like(slot)
        lo, hi, _ = lax.fori_loop(
            0, n_entries // _UNROLL, entries, (zero, zero, key + 1)
        )
        c = lax.shift_right_logical(lo, _IDX_BITS)
        return lo & _IDX_MASK, c, lax.shift_right_logical(hi, _IDX_BITS) - c

    def fetch(ptr):
        p = jnp.maximum(ptr, 0)
        q = lax.shift_right_logical(p, 1)

        def pairs(e, w):
            for u in range(_UNROLL):
                k = e * _UNROLL + u
                w = jnp.where(q == k, words_ref[k], w)
            return w

        pair = lax.fori_loop(0, n_pairs // _UNROLL, pairs, jnp.zeros_like(ptr))
        return jnp.where((p & 1) == 1, lax.shift_right_logical(pair, 16), pair & 0xFFFF)

    def step(t, carry):
        x, ptr = carry
        slot = x & mask
        s, c, f = lookup(slot)
        x = f * lax.shift_right_logical(x, precision) + slot - c
        need = lax.shift_right_logical(x, 16) == 0
        x = jnp.where(need, lax.shift_left(x, 16) | fetch(ptr), x)
        ptr = jnp.where(need, ptr - 1, ptr)
        syms_ref[t] = s
        return x, ptr

    n = jnp.minimum(tile, n_sym - j * tile)  # the last tile may be short
    x, ptr = lax.fori_loop(0, n, step, (x_ref[...], p_ref[...]))
    x_ref[...] = x
    p_ref[...] = ptr


@functools.partial(jax.jit, static_argnames=("precision", "n_sym", "interpret"))
def rans_decode_pallas(
    words: jnp.ndarray,  # (n_lanes, cap) uint16
    n_words: jnp.ndarray,  # (n_lanes,) int32
    state: jnp.ndarray,  # (n_lanes,) uint32
    rows: jnp.ndarray,  # (A', R, 128) int32, as lane_rows lays them out
    blocks: jnp.ndarray,  # (ceil(n_lanes / 1024),) int32 row block per lane block
    *,
    precision: int,
    n_sym: int,
    interpret: bool = False,
):
    """Decode ``n_sym`` symbols per lane; returns ``(symbols (n_lanes,
    n_sym) uint16, final state (n_lanes,) uint32, final ptr (n_lanes,)
    int32)``, as ``rans._decode_impl``'s scan does.

    Lane block ``b`` (lanes ``1024 b`` on) reads row block ``blocks[b]`` of
    ``rows``: its lane ``k`` decodes with ``rows[:, 8 blocks[b] + k // 128,
    k % 128]``.  Lanes are padded to whole blocks (state ``2^16``, no words)
    and words to whole loop iterations.
    """
    n_lanes, cap = words.shape
    if n_sym == 0:
        return (jnp.zeros((n_lanes, 0), jnp.uint16), state.astype(jnp.uint32),
                n_words.astype(jnp.int32) - 1)
    lanes = _round_up(n_lanes, BLOCK_LANES)
    pad = lanes - n_lanes
    n_blocks = lanes // BLOCK_LANES
    n_entries = rows.shape[0]
    if blocks.shape != (n_blocks,) or n_entries % _UNROLL:
        raise ValueError(f"rows {rows.shape} / blocks {blocks.shape} do not fit {n_lanes} lanes")
    need = vmem_bytes(n_entries, cap, n_sym)
    if need > _VMEM_MAX:
        raise ValueError(
            f"rANS decode needs {need} B of VMEM for {n_entries} row entries and "
            f"word cap {cap}, over the {_VMEM_MAX} B it may take"
        )
    tile = min(n_sym, SYM_TILE)
    n_tiles = -(-n_sym // tile)
    cap_p = _round_up(max(cap, 1), 2 * _UNROLL)
    w = jnp.pad(words.astype(jnp.int32), ((0, pad), (0, cap_p - cap)))
    w = w[:, 0::2] | lax.shift_left(w[:, 1::2], 16)  # word 2k low, 2k + 1 high
    words_l = w.T.reshape(cap_p // 2, lanes // _LANE, _LANE)
    x0 = jnp.pad(
        lax.bitcast_convert_type(state.astype(jnp.uint32), jnp.int32),
        (0, pad), constant_values=1 << 16,
    ).reshape(lanes // _LANE, _LANE)
    ptr0 = jnp.pad(n_words.astype(jnp.int32) - 1, (0, pad), constant_values=-1)
    ptr0 = ptr0.reshape(lanes // _LANE, _LANE)

    lane_block = pl.BlockSpec((_SUB, _LANE), lambda i, j, blk: (i, 0))
    syms, x, ptr = pl.pallas_call(
        functools.partial(
            _rans_decode_kernel, precision=precision, n_sym=n_sym, tile=tile
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_blocks, n_tiles),
            in_specs=[
                pl.BlockSpec((n_entries, _SUB, _LANE), lambda i, j, blk: (0, blk[i], 0)),
                pl.BlockSpec((cap_p // 2, _SUB, _LANE), lambda i, j, blk: (0, i, 0)),
                lane_block,
                lane_block,
            ],
            out_specs=[
                pl.BlockSpec((tile, _SUB, _LANE), lambda i, j, blk: (j, i, 0)),
                lane_block,
                lane_block,
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n_tiles * tile, lanes // _LANE, _LANE), jnp.int32),
            jax.ShapeDtypeStruct((lanes // _LANE, _LANE), jnp.int32),
            jax.ShapeDtypeStruct((lanes // _LANE, _LANE), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=need + _VMEM_SLACK,
        ),
        interpret=interpret,
        name="rans_decode",
    )(blocks.astype(jnp.int32), rows, words_l, x0, ptr0)
    syms = syms[:n_sym].reshape(n_sym, lanes)[:, :n_lanes].T.astype(jnp.uint16)
    x = lax.bitcast_convert_type(x.reshape(lanes)[:n_lanes], jnp.uint32)
    return syms, x, ptr.reshape(lanes)[:n_lanes]
