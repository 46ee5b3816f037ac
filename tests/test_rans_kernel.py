"""The Pallas rANS decode kernel equals the scan bit for bit.

The kernel (``kernels/rans_decode.py``) is what ``rans.decode`` runs on a
TPU; here ``rans.decode``'s path runs it in interpret mode against
``rans._decode_scan``, the CPU path, on the table stacks and stream shapes
the codec hands it: symbols, final state and final word pointer must all
agree, on valid streams and on arbitrary ones (``decode(check=True)`` reads
the last two), with rows gathered per call and with a stack's rows laid
out once.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import codec, rans, tables
from repro.kernels.rans_decode import BLOCK_LANES, SYM_TILE, lane_rows, rans_decode_pallas


def _use_kernel(monkeypatch):
    """``rans.decode`` runs the kernel, interpreted on the CPU."""
    monkeypatch.setattr(rans, "decoder", lambda: "kernel")
    monkeypatch.setattr(rans, "_KERNEL_INTERPRET", True)


def _tables(rng, n_tables, A, precision):
    counts = rng.integers(0, 1000, size=(n_tables, A)) * (rng.random((n_tables, A)) < 0.3)
    return tables.build_coder_tables(tables.normalize_freqs(counts, precision), precision)


def _streams(rng, tab, t_idx, n_sym, cap=None):
    """Symbols drawn from each lane's table, encoded; words padded to
    ``cap`` (default: ``codec._CAP_BUCKET``, as ``_stack_streams`` pads)."""
    M = 1 << tab.precision
    slots = rng.integers(0, M, size=(len(t_idx), n_sym))
    syms = np.asarray(tab.slot2sym)[t_idx[:, None], slots]
    w, n, x = rans.encode(jnp.asarray(syms), jnp.asarray(t_idx), tab)
    w, n = np.asarray(w), np.asarray(n)
    used = max(int(n.max()), 1)
    cap = cap or -(-used // codec._CAP_BUCKET) * codec._CAP_BUCKET
    words = np.zeros((len(t_idx), cap), np.uint16)
    words[:, :used] = w[:, :used]
    return syms, words, n.astype(np.int32), np.asarray(x)


def _case(name, rng):
    """(stack, table_idx, words, n_words, state, n_sym, symbols or None)."""
    precision = 12 if name == "precision-12" else 11
    n_tab = BLOCK_LANES if name == "laid-out" else 5
    if name in ("anchors", "laid-out"):
        sets = [_tables(rng, n_tab, 256, precision) for _ in range(2)]
        stack = rans.stack_tables(sets)
        n_lanes, n_sym, fams = 700, 26, [(0, sets[0]), (n_tab, sets[1])]
    else:  # the delta decode stack: 4 lossy sets and the lossless one, padded
        sets = [_tables(rng, n_tab, 255, precision) for _ in range(4)]
        sets.append(_tables(rng, n_tab, 509, precision))
        stack = rans.stack_tables(sets, pad_alphabet=True)
        n_lanes, n_sym = 2 * BLOCK_LANES + 300, 40
        fams = [(n_tab * i, t) for i, t in enumerate(sets)]
        if name == "zero-words":
            n_sym = 2
        if name == "long-chunk":  # three symbol tiles, the last one short
            n_lanes, n_sym = 300, 2 * SYM_TILE + 21
    if name == "laid-out":
        # whole blocks of consecutive tables, as the codec's lanes read a
        # stack: set 1, then set 0, then a partial block of set 1
        n_lanes = 2 * BLOCK_LANES + 300
        fam = np.repeat([1, 0, 1], BLOCK_LANES)[:n_lanes]
        local = (np.arange(n_lanes) % BLOCK_LANES).astype(np.int32)
    else:  # lossy and lossless lanes interleaved in one call
        fam = rng.integers(0, len(fams), n_lanes)
        local = rng.integers(0, n_tab, n_lanes).astype(np.int32)
    syms = np.zeros((n_lanes, n_sym), np.uint16)
    cap = 128 if name == "cap-bucket" else None
    parts = []
    for f, (base, tab) in enumerate(fams):
        sel = np.flatnonzero(fam == f)
        s, w, n, x = _streams(rng, tab, local[sel], n_sym, cap)
        syms[sel] = s
        parts.append((sel, w, n, x))
    cap = max(w.shape[1] for _, w, _, _ in parts)
    words = np.zeros((n_lanes, cap), np.uint16)
    n_words = np.zeros(n_lanes, np.int32)
    state = np.zeros(n_lanes, np.uint32)
    for sel, w, n, x in parts:
        words[sel, : w.shape[1]], n_words[sel], state[sel] = w, n, x
    t_idx = (local + np.asarray([fams[f][0] for f in fam])).astype(np.int32)
    if name == "zero-words":
        assert (n_words == 0).any()
    if name == "high-states":  # arbitrary streams: states in [2^31, 2^32)
        state = rng.integers(1 << 31, 1 << 32, n_lanes, dtype=np.uint64).astype(np.uint32)
        n_words = rng.integers(0, cap + 1, n_lanes).astype(np.int32)
        words = rng.integers(0, 1 << 16, words.shape).astype(np.uint16)
        syms = None
    return stack, t_idx, words, n_words, state, n_sym, syms


CASES = [
    "anchors", "deltas-mixed", "precision-12", "zero-words", "high-states",
    "cap-bucket", "laid-out", "long-chunk",
]


@pytest.mark.parametrize("name", CASES)
def test_kernel_equals_scan(name, monkeypatch):
    rng = np.random.default_rng(CASES.index(name))
    stack, t_idx, words, n_words, state, n_sym, syms = _case(name, rng)
    args = (jnp.asarray(words), jnp.asarray(n_words), jnp.asarray(state), jnp.asarray(t_idx))
    assert len(t_idx) % BLOCK_LANES  # lanes that do not fill the last block
    want = rans._decode_scan(*args, stack.freqs, stack.cums, stack.slot2sym,
                             stack.precision, n_sym)
    _use_kernel(monkeypatch)
    if name == "laid-out":  # a decode stack as the codec holds it
        stack = rans.decode_tables(stack)
        assert stack.slot2sym is None and stack.rows is not None
    # the rows are read in place only where the stack is laid out
    blocks = rans._row_blocks(t_idx, stack.rows)
    assert (blocks is not None) == (name == "laid-out")
    got = rans._decode_all(words, n_words, state, t_idx, stack, n_sym)
    for g, w, what in zip(got, want, ("symbols", "final state", "final ptr")):
        assert g.dtype == w.dtype and g.shape == w.shape, what
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=what)
    if syms is not None:  # a valid stream decodes to its symbols and back home
        np.testing.assert_array_equal(np.asarray(got[0]), syms)
        assert (np.asarray(got[1]) == 1 << 16).all() and (np.asarray(got[2]) == -1).all()


def test_check_refuses_a_corrupt_stream_on_the_kernel(monkeypatch):
    """``decode(check=True)`` through the kernel path: a clean stream
    passes, one flipped word is refused."""
    _use_kernel(monkeypatch)
    rng = np.random.default_rng(7)
    tab = _tables(rng, 3, 256, 11)
    t_idx = rng.integers(0, 3, 200).astype(np.int32)
    syms, words, n_words, state = _streams(rng, tab, t_idx, 26)
    got = rans.decode(words, n_words, state, t_idx, tab, 26, check=True)
    np.testing.assert_array_equal(np.asarray(got), syms)
    lane = int(np.argmax(n_words))
    words = words.copy()
    words[lane, 0] ^= 0x5A5A
    with pytest.raises(ValueError, match="corrupt"):
        rans.decode(words, n_words, state, t_idx, tab, 26, check=True)


def test_scan_derives_its_lookup_from_laid_out_tables():
    """A stack laid out for the kernel keeps no ``slot2sym``; the scan, the
    kernel's oracle, then derives it from ``cums`` and decodes the same."""
    rng = np.random.default_rng(8)
    tab = rans.stack_tables([_tables(rng, 3, 255, 11), _tables(rng, 2, 509, 11)],
                            pad_alphabet=True)
    t_idx = rng.integers(0, 5, 300).astype(np.int32)
    _, words, n_words, state = _streams(rng, tab, t_idx, 30)
    args = (jnp.asarray(words), jnp.asarray(n_words), jnp.asarray(state), jnp.asarray(t_idx))
    want = rans._decode_scan(*args, tab.freqs, tab.cums, tab.slot2sym, 11, 30)
    got = rans._decode_scan(*args, tab.freqs, tab.cums, None, 11, 30)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_kernel_refuses_blocks_over_its_vmem():
    """VMEM holds one block's rows and words and one tile of symbols; a
    word cap past what that allows is refused by name, not left to fail
    in the compiler."""
    tab = _tables(np.random.default_rng(9), 2, 509, 11)
    rows = lane_rows(tab.cums, 11)
    blocks = jnp.zeros((1,), jnp.int32)
    n = jnp.zeros((4,), jnp.int32)
    x = jnp.full((4,), 1 << 16, jnp.uint32)
    rans_decode_pallas(jnp.zeros((4, 1856), jnp.uint16), n, x, rows, blocks,
                       precision=11, n_sym=1, interpret=True)
    with pytest.raises(ValueError, match="VMEM"):
        rans_decode_pallas(jnp.zeros((4, 1 << 14), jnp.uint16), n, x, rows, blocks,
                           precision=11, n_sym=1, interpret=True)
