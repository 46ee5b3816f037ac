"""Lane-parallel static-table rANS entropy coder, as a JAX program.

This is the TPU-native adaptation of CacheGen's GPU arithmetic coder: the
paper runs one CUDA thread per token's bitstream; on TPU the analogue of
"thousands of independent sequential coders" is a vectorized ``lax.scan``
where every *lane* carries its own 32-bit coder state.  Lanes map to
(layer, K/V, channel) streams so that each lane uses exactly one static
symbol distribution (paper Insight 3: per-channel-per-layer distributions),
which keeps table gathers uniform.

rANS (range asymmetric numeral systems) is in the same entropy-coding family
as arithmetic coding — both approach the entropy bound; we verify in tests
that compressed sizes match an exact arithmetic-coding oracle within ~1%.
rANS is chosen over a bit-level AC port because it is table-driven and
carry-free: the inner loop is a handful of integer ops and one table lookup,
with no CUDA-style bit/carry manipulation, which has no TPU analogue.

Decode by platform (:func:`decoder`): on CPU the scan looks symbols up with
per-lane gathers, which are cheap there; it is also the oracle.  On a TPU
those gathers are random reads from hundreds of MB of tables in HBM, so
:func:`decode` runs the Pallas kernel ``kernels/rans_decode.py`` instead:
per block of 1024 lanes it keeps the state, the lanes' cumulative-frequency
rows and their words in VMEM and finds each symbol by compare-count over the
row, with the same integer updates bit for bit.  A decode stack is laid out
for its decoder once (:func:`decode_tables`): for the kernel, its rows
lane-major, which the codec's lanes read in place, and no ``slot2sym``,
which only the scan reads.  Both run inside the one jitted
``_decode_impl``, whose program name the benchmark reads.  Encoding is
offline and stays a scan.

Variant: 32-bit state, 16-bit word renormalization (ryg_rans "rans_word").
With precision ``k <= 14`` and all frequencies >= 1 (< 2^k), each symbol
emits/consumes at most one 16-bit word, so the scan does fixed work per step.

Wire format per call: ``words (n_lanes, n_sym) uint16`` buffer of which the
first ``n_words[lane]`` entries are valid, plus the 4-byte final state per
lane.  The decoder reads words in reverse emission order (rANS is LIFO).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.rans_decode import BLOCK_LANES, lane_rows, rans_decode_pallas

__all__ = [
    "CoderTables", "encode", "decode", "decoder", "decode_tables", "encoded_bytes",
    "stack_tables",
]

RANS_L = jnp.uint32(1 << 16)  # lower bound of the normalized state interval
_U32_ONE = jnp.uint32(1)


class CoderTables(NamedTuple):
    """Static rANS tables for ``n_tables`` distributions over alphabet A.

    freqs: (n_tables, A) uint32, each row sums to 2**precision, all >= 1
    cums:  (n_tables, A + 1) uint32 exclusive prefix sums
    slot2sym: (n_tables, 2**precision) uint16, or None where the tables are
        laid out for the kernel (the scan then derives it from ``cums``)
    precision: int (static)
    rows: the kernel's lane-major layout of ``cums`` (``lane_rows``), or
        None; see :func:`decode_tables`
    """

    freqs: jnp.ndarray
    cums: jnp.ndarray
    slot2sym: Optional[jnp.ndarray]
    precision: int
    rows: Optional[jnp.ndarray] = None

    @property
    def alphabet(self) -> int:
        return self.freqs.shape[-1]

    @property
    def n_tables(self) -> int:
        return self.freqs.shape[0]


@functools.partial(jax.jit, static_argnames=("precision",))
def _encode_impl(
    symbols: jnp.ndarray,  # (n_lanes, n_sym) uint16/int32
    table_idx: jnp.ndarray,  # (n_lanes,) int32
    freqs: jnp.ndarray,
    cums: jnp.ndarray,
    precision: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    n_lanes, n_sym = symbols.shape
    A = freqs.shape[-1]
    freqs_flat = freqs.reshape(-1)
    cums_flat = cums.reshape(-1)
    lane = jnp.arange(n_lanes, dtype=jnp.int32)
    t_base_f = table_idx.astype(jnp.int32) * A
    t_base_c = table_idx.astype(jnp.int32) * (A + 1)
    k = jnp.uint32(precision)
    shift16 = jnp.uint32(16)

    # rANS encodes in reverse symbol order so the decoder runs forward.
    xs = jnp.flip(symbols.astype(jnp.int32).T, axis=0)  # (n_sym, n_lanes)

    def step(carry, s):
        x, ptr, buf = carry
        f = freqs_flat[t_base_f + s]
        c = cums_flat[t_base_c + s]
        # renormalize: emit one 16-bit word if x would overflow
        x_max = ((RANS_L >> k) << shift16) * f
        emit = x >= x_max
        word = (x & jnp.uint32(0xFFFF)).astype(jnp.uint16)
        buf = buf.at[lane, ptr].set(word)
        ptr = ptr + emit.astype(jnp.int32)
        x = jnp.where(emit, x >> shift16, x)
        # C(s, x) = (x // f) << k + (x % f) + c
        q = x // f
        r = x - q * f
        x = (q << k) + r + c
        return (x, ptr, buf), None

    x0 = jnp.full((n_lanes,), RANS_L, dtype=jnp.uint32)
    ptr0 = jnp.zeros((n_lanes,), dtype=jnp.int32)
    buf0 = jnp.zeros((n_lanes, n_sym), dtype=jnp.uint16)
    (x, ptr, buf), _ = jax.lax.scan(step, (x0, ptr0, buf0), xs)
    return buf, ptr, x


def _decode_scan(
    words: jnp.ndarray,  # (n_lanes, cap) uint16
    n_words: jnp.ndarray,  # (n_lanes,) int32
    state: jnp.ndarray,  # (n_lanes,) uint32
    table_idx: jnp.ndarray,  # (n_lanes,) int32
    freqs: jnp.ndarray,
    cums: jnp.ndarray,
    slot2sym: Optional[jnp.ndarray],
    precision: int,
    n_sym: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The decode as a ``lax.scan`` of per-lane table gathers: the CPU path
    and the oracle of the TPU kernel."""
    n_lanes = words.shape[0]
    A = freqs.shape[-1]
    M = 1 << precision
    if slot2sym is None:  # tables laid out for the kernel: derive the lookup
        slots = jnp.arange(M, dtype=jnp.uint32)
        slot2sym = jax.vmap(lambda c: jnp.searchsorted(c[1:], slots, side="right"))(cums)
    freqs_flat = freqs.reshape(-1)
    cums_flat = cums.reshape(-1)
    s2s_flat = slot2sym.reshape(-1)
    lane = jnp.arange(n_lanes, dtype=jnp.int32)
    t_base_f = table_idx.astype(jnp.int32) * A
    t_base_c = table_idx.astype(jnp.int32) * (A + 1)
    t_base_m = table_idx.astype(jnp.int32) * M
    k = jnp.uint32(precision)
    mask = jnp.uint32(M - 1)
    shift16 = jnp.uint32(16)

    def step(carry, _):
        x, ptr = carry
        slot = (x & mask).astype(jnp.int32)
        s = s2s_flat[t_base_m + slot].astype(jnp.int32)
        f = freqs_flat[t_base_f + s]
        c = cums_flat[t_base_c + s]
        x = f * (x >> k) + slot.astype(jnp.uint32) - c
        need = x < RANS_L
        word = words[lane, jnp.maximum(ptr, 0)].astype(jnp.uint32)
        x = jnp.where(need, (x << shift16) | word, x)
        ptr = ptr - need.astype(jnp.int32)
        return (x, ptr), s.astype(jnp.uint16)

    x0 = state.astype(jnp.uint32)
    ptr0 = n_words.astype(jnp.int32) - 1
    (x, ptr), syms = jax.lax.scan(step, (x0, ptr0), None, length=n_sym)
    return syms.T, x, ptr  # symbols (n_lanes, n_sym) in forward order


def decoder() -> str:
    """The decoder :func:`decode` runs on this process's backend: the Pallas
    kernel on a TPU, the scan elsewhere."""
    return "kernel" if jax.default_backend() == "tpu" else "scan"


_KERNEL_INTERPRET = False  # the kernel runs natively; tests interpret it on CPU


@functools.partial(
    jax.jit, static_argnames=("precision", "n_sym", "kernel", "interpret")
)
def _decode_impl(
    words: jnp.ndarray,  # (n_lanes, cap) uint16
    n_words: jnp.ndarray,  # (n_lanes,) int32
    state: jnp.ndarray,  # (n_lanes,) uint32
    table_idx: jnp.ndarray,  # (n_lanes,) int32
    freqs: jnp.ndarray,
    cums: jnp.ndarray,
    slot2sym: Optional[jnp.ndarray],
    rows: Optional[jnp.ndarray],  # the tables' kernel layout, or None
    blocks: Optional[jnp.ndarray],  # each lane block's block of ``rows``
    *,
    precision: int,
    n_sym: int,
    kernel: bool,
    interpret: bool,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The scan, or the kernel with the tables' laid-out ``rows``; without
    them the kernel reads this call's lanes' rows, laid out here."""
    if not kernel:
        return _decode_scan(
            words, n_words, state, table_idx, freqs, cums, slot2sym, precision, n_sym
        )
    if rows is None:
        rows = lane_rows(cums[table_idx], precision)
        blocks = jnp.arange(rows.shape[1] // 8, dtype=jnp.int32)
    return rans_decode_pallas(
        words, n_words, state, rows, blocks, precision=precision, n_sym=n_sym,
        interpret=interpret,
    )


def _row_blocks(table_idx: np.ndarray, rows: Optional[jnp.ndarray]) -> Optional[np.ndarray]:
    """Each lane block's block of the tables' ``rows``, where every block's
    lanes read consecutive tables from a multiple of 1024 on (a decode
    stack's lanes do: one table per lane, sets of whole blocks); else None."""
    n = table_idx.shape[0]
    if rows is None or n == 0:
        return None
    start = table_idx - np.arange(n, dtype=np.int32) % BLOCK_LANES
    blocks = start[::BLOCK_LANES]
    if (blocks % BLOCK_LANES).any() or (np.repeat(blocks, BLOCK_LANES)[:n] != start).any():
        return None
    return blocks // BLOCK_LANES


def decode_tables(tables: CoderTables) -> CoderTables:
    """``tables`` as this backend's decoder reads them, for a decode stack
    built once: for the kernel, with its lane-major ``rows`` and without
    ``slot2sym``, which only the scan reads; for the scan, unchanged."""
    if decoder() != "kernel" or tables.rows is not None:
        return tables
    return tables._replace(slot2sym=None, rows=lane_rows(tables.cums, tables.precision))


def encode(
    symbols: jnp.ndarray,
    table_idx: jnp.ndarray,
    tables: CoderTables,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Encode ``symbols[(lane, t)]`` -> (words, n_words, final_state)."""
    if symbols.ndim != 2:
        raise ValueError(f"symbols must be (n_lanes, n_sym), got {symbols.shape}")
    return _encode_impl(
        jnp.asarray(symbols),
        jnp.asarray(table_idx, dtype=jnp.int32),
        tables.freqs,
        tables.cums,
        tables.precision,
    )


def _decode_all(
    words, n_words, state, table_idx, tables: CoderTables, n_sym: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Symbols, final states and final word pointers, by :func:`decoder`."""
    kernel = decoder() == "kernel"
    blocks = _row_blocks(np.asarray(table_idx, np.int32), tables.rows) if kernel else None
    return _decode_impl(
        jnp.asarray(words),
        jnp.asarray(n_words, dtype=jnp.int32),
        jnp.asarray(state),
        jnp.asarray(table_idx, dtype=jnp.int32),
        tables.freqs,
        tables.cums,
        tables.slot2sym,
        tables.rows if blocks is not None else None,
        blocks,
        precision=tables.precision,
        n_sym=n_sym,
        kernel=kernel,
        interpret=_KERNEL_INTERPRET,
    )


def decode(
    words: jnp.ndarray,
    n_words: jnp.ndarray,
    state: jnp.ndarray,
    table_idx: jnp.ndarray,
    tables: CoderTables,
    n_sym: int,
    check: bool = False,
) -> jnp.ndarray:
    """Decode ``n_sym`` symbols per lane.  Exact inverse of :func:`encode`."""
    syms, x, ptr = _decode_all(words, n_words, state, table_idx, tables, n_sym)
    if check:
        x = np.asarray(x)
        ptr = np.asarray(ptr)
        if not (x == np.uint32(1 << 16)).all() or not (ptr == -1).all():
            raise ValueError(
                "rANS stream corrupt: decoder did not return to initial state"
            )
    return syms


def encoded_bytes(n_words: jnp.ndarray) -> int:
    """Wire size: valid 16-bit words + 4-byte final state per lane."""
    n_words = np.asarray(n_words)
    return int(n_words.sum()) * 2 + 4 * n_words.shape[0]


def stack_tables(
    tabs: "list[CoderTables] | tuple[CoderTables, ...]",
    pad_alphabet: bool = False,
) -> CoderTables:
    """Concatenate several table sets into one along the table axis.

    This is what makes *batched* multi-stream (de)coding possible: streams
    that use different table sets (e.g. different lossy levels, or lossless
    vs lossy anchors) are stacked along the lane axis into one ``encode`` /
    ``decode`` call, with each lane's ``table_idx`` offset by the cumulative
    table count of the sets before it.  Requires identical precision; by
    default also identical alphabets.

    ``pad_alphabet=True`` additionally merges sets with *different*
    alphabets by zero-padding each ``freqs`` row (and edge-padding ``cums``)
    to the widest alphabet.  This is sound for **decoding only**: the
    decoder reads ``freqs[s]``/``cums[s]`` exclusively for symbols ``s``
    produced by ``slot2sym`` (always < the set's true alphabet), so the
    padding is never touched (nor by the kernel's compare-count: an
    edge-padded entry equals ``M``).  Padded tables must not be used to encode —
    a padded symbol id would emit a zero-frequency state transition.
    """
    if not tabs:
        raise ValueError("need at least one CoderTables to stack")
    precision = tabs[0].precision
    A = max(t.alphabet for t in tabs)
    for t in tabs:
        if t.precision != precision:
            raise ValueError(
                f"stack_tables requires identical precision, got "
                f"{[t.precision for t in tabs]}"
            )
        if t.alphabet != A and not pad_alphabet:
            raise ValueError(
                "stack_tables requires identical alphabets (or "
                f"pad_alphabet=True), got {[t.alphabet for t in tabs]}"
            )
    if len(tabs) == 1:
        return tabs[0]

    def _padded(t: CoderTables):
        if t.alphabet == A:
            return t.freqs, t.cums
        pad = A - t.alphabet
        freqs = jnp.pad(t.freqs, ((0, 0), (0, pad)))
        cums = jnp.pad(t.cums, ((0, 0), (0, pad)), mode="edge")
        return freqs, cums

    parts = [_padded(t) for t in tabs]
    return CoderTables(
        freqs=jnp.concatenate([f for f, _ in parts], axis=0),
        cums=jnp.concatenate([c for _, c in parts], axis=0),
        # only the scan reads slot2sym; the kernel's platform leaves it out
        slot2sym=None
        if decoder() == "kernel" or any(t.slot2sym is None for t in tabs)
        else jnp.concatenate([t.slot2sym for t in tabs], axis=0),
        precision=precision,
    )
