"""CacheGen KV-cache codec: chunk-level encode/decode at multiple levels.

Pipeline (paper §5.2):

    KV (L, 2, T, C) f32
      └─ split into token groups of ``group_size``; anchor = first token
         ├─ anchors: 8-bit vectorwise quantization            (quant.py)
         ├─ deltas: layer-group binned quantization           (quant.py)
         └─ symbols → lane-parallel rANS with per-(layer,K/V,channel)
            static distributions                              (rans.py)
      → bitstream (bitstream.py)

Encoding levels:
  * level 0: "lossless-after-8bit" — entropy coding of 8-bit quantized KV
    (paper's lossless configuration, 1.67–1.81× claim);
  * level 1..n: lossy, bins scaled by ``level_mults[level-1]``
    (level 1 finest; higher level = smaller stream, coarser KV).

Tables must be profiled offline per model on calibration KV caches
(:func:`profile`), matching the paper's offline per-model profiling.

Fused-path / oracle split (PR 1): the serving hot path is
:func:`decode_chunks` — a *batched* decode that parses every fetched chunk's
bitstream once on the host, stacks all lanes into exactly two rANS decodes
(anchors for all chunks; deltas for all chunks — mixed lossy levels *and*
the lossless family share the delta decode via alphabet-padded
:func:`rans.stack_tables` table stacking), then reconstructs
every chunk's tokens in a single jitted assemble step that drives the fused
Pallas kernels in ``kernels/kvquant.py`` (dequant + anchor-broadcast-add +
dtype cast in one HBM pass, emitting whole token groups).  No intermediate
f32 ``(L, 2, T, C)`` tensor, no per-chunk host round-trips, no per-chunk
device dispatch.  :func:`decode_chunk` (singular) is the retained unfused
reference path — the correctness oracle the fused path is tested against
(bit-exact at level 0, tolerance-exact at lossy levels).

Each rANS decode runs by platform (:func:`rans.decoder`): on a TPU the
gather-free Pallas kernel ``kernels/rans_decode.py``, elsewhere the
``lax.scan`` of table gathers, which is also the kernel's oracle.  The
``codec.dispatch`` span's ``rans`` stat names the one that ran.

Mirror-image encode batching: :func:`encode_all_levels` symbolizes and
entropy-codes the (level-invariant) anchors once, and runs all lossy levels'
delta rANS encodes as one stacked call; its per-level bitstreams are
byte-identical to per-level :func:`encode_chunk`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import bitstream, gop, quant, rans, tables

__all__ = [
    "CodecConfig",
    "CodecTables",
    "profile",
    "encode_chunk",
    "peek_chunk_header",
    "verify_chunk",
    "decode_chunk",
    "decode_chunks",
    "decode_chunk_runs",
    "encode_all_levels",
    "ensure_stacks",
    "kv_nbytes_fp16",
    "kv_nbytes_int8",
]


@dataclasses.dataclass(frozen=True)
class CodecConfig:
    group_size: int = 10
    layer_group_bins: Tuple[float, float, float] = (0.5, 1.0, 1.5)
    level_mults: Tuple[float, ...] = (0.5, 1.0, 2.0, 4.0)
    delta_qmax: int = 127
    precision: int = 12
    channel_buckets: Optional[int] = None
    use_delta_scale: bool = True

    @property
    def n_levels(self) -> int:
        return 1 + len(self.level_mults)

    @property
    def delta_alphabet(self) -> int:
        return quant.delta_alphabet(self.delta_qmax)


class CodecTables(NamedTuple):
    """Per-model static coder tables (profiled offline)."""

    anchor: rans.CoderTables  # lossy anchors, alphabet 256
    deltas: Dict[int, rans.CoderTables]  # per lossy level, alphabet 2*qmax+1
    ll_anchor: rans.CoderTables  # lossless anchors, alphabet 256
    ll_delta: rans.CoderTables  # lossless integer deltas, alphabet 509
    table_idx: np.ndarray  # lane -> table
    delta_scale: Optional[np.ndarray]  # (L, 2) or None
    config: CodecConfig
    n_layers: int
    n_channels: int
    # Pre-stacked table sets for the batched coder calls (built by
    # :func:`profile`; lazily derived when tables are constructed by hand).
    anchor_stack: Optional[rans.CoderTables] = None  # [anchor; ll_anchor]
    lossy_delta_stack: Optional[rans.CoderTables] = None  # deltas lvl 1..n
    # decode-only: all delta sets (lossy levels + lossless) alphabet-padded
    # into one stack so mixed-level runs need a single delta decode
    delta_decode_stack: Optional[rans.CoderTables] = None


def _anchor_stack(ct: CodecTables) -> rans.CoderTables:
    if ct.anchor_stack is not None:
        return ct.anchor_stack
    return rans.stack_tables([ct.anchor, ct.ll_anchor])


def _lossy_delta_stack(ct: CodecTables) -> rans.CoderTables:
    if ct.lossy_delta_stack is not None:
        return ct.lossy_delta_stack
    return rans.stack_tables([ct.deltas[l] for l in sorted(ct.deltas)])


def _delta_decode_stack(ct: CodecTables) -> rans.CoderTables:
    if ct.delta_decode_stack is not None:
        return ct.delta_decode_stack
    lossy = [ct.deltas[l] for l in sorted(ct.deltas)]
    return rans.stack_tables(lossy + [ct.ll_delta], pad_alphabet=True)


def _delta_table_base(ct: CodecTables, level: int) -> int:
    """Table offset of ``level``'s delta set inside the decode stack."""
    n_td = ct.ll_delta.n_tables
    return len(ct.deltas) * n_td if level == 0 else (level - 1) * n_td


def ensure_stacks(ct: CodecTables) -> CodecTables:
    """Fill in any missing pre-stacked table sets (one-time upgrade).

    Tables built by :func:`profile` already carry them; tables constructed
    by hand or unpickled from pre-stack assets default the fields to None,
    which would otherwise rebuild + re-upload the stacks on every batched
    coder call.  Long-lived holders (e.g. ``KVStore``) call this once.  The
    decode stacks are laid out for this backend's rANS decoder
    (:func:`rans.decode_tables`).
    """
    return ct._replace(
        anchor_stack=rans.decode_tables(_anchor_stack(ct)),
        lossy_delta_stack=_lossy_delta_stack(ct) if ct.deltas else None,
        delta_decode_stack=rans.decode_tables(_delta_decode_stack(ct)),
    )


def _lanes(x: jnp.ndarray) -> jnp.ndarray:
    """(L, 2, T', C) -> (L*2*C, T') lane-major symbol matrix."""
    L, two, Tp, C = x.shape
    return jnp.transpose(x, (0, 1, 3, 2)).reshape(L * two * C, Tp)


def _unlanes(x: jnp.ndarray, L: int, C: int) -> jnp.ndarray:
    n_lanes, Tp = x.shape
    return jnp.transpose(x.reshape(L, 2, C, Tp), (0, 1, 3, 2))


def _bins_for_level(
    cfg: CodecConfig, L: int, level: int, delta_scale: Optional[np.ndarray]
) -> np.ndarray:
    mult = cfg.level_mults[level - 1]
    ds = delta_scale if cfg.use_delta_scale else None
    return quant.effective_bins(L, cfg.layer_group_bins, mult, ds)


def _symbolize(
    kv: jnp.ndarray,
    cfg: CodecConfig,
    level: int,
    delta_scale: Optional[np.ndarray],
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, gop.GroupLayout]:
    """KV -> (anchor_symbols_lanes, delta_symbols_lanes, scales, layout)."""
    L, two, T, C = kv.shape
    layout = gop.make_layout(T, cfg.group_size)
    if level == 0:
        a_sym, d_sym, scales = quant.lossless_quantize(kv, layout)
    else:
        anchors, deltas = gop.split_anchors_deltas(kv, layout)
        a_sym, scales = quant.quantize_anchors(anchors)
        bins = jnp.asarray(_bins_for_level(cfg, L, level, delta_scale))
        d_sym = quant.quantize_deltas(deltas, bins, cfg.delta_qmax)
    return _lanes(a_sym), _lanes(d_sym), scales, layout


def profile(
    kv_samples: Sequence[np.ndarray],
    cfg: CodecConfig = CodecConfig(),
) -> CodecTables:
    """Offline table profiling from calibration KV caches (paper §5.2).

    kv_samples: list of (L, 2, T, C) arrays from representative contexts.
    """
    if not kv_samples:
        raise ValueError("need at least one calibration KV cache")
    L, two, _, C = kv_samples[0].shape
    n_t = tables.n_tables_for(L, C, cfg.channel_buckets)
    t_idx = tables.lane_table_index(L, C, cfg.channel_buckets)

    delta_scale = None
    if cfg.use_delta_scale:
        acc = np.zeros((L, 2), np.float64)
        n = 0
        for kv in kv_samples:
            layout = gop.make_layout(kv.shape[2], cfg.group_size)
            _, deltas = gop.split_anchors_deltas(jnp.asarray(kv, jnp.float32), layout)
            acc += np.asarray(jnp.mean(deltas.astype(jnp.float32) ** 2, axis=(2, 3)))
            n += 1
        delta_scale = np.sqrt(acc / n).astype(np.float32)
        delta_scale = np.maximum(delta_scale, 1e-6)

    a_counts = np.zeros((n_t, quant.ANCHOR_ALPHABET), np.int64)
    lla_counts = np.zeros((n_t, quant.ANCHOR_ALPHABET), np.int64)
    lld_counts = np.zeros((n_t, quant.lossless_delta_alphabet()), np.int64)
    d_counts = {
        lvl: np.zeros((n_t, cfg.delta_alphabet), np.int64)
        for lvl in range(1, cfg.n_levels)
    }
    for kv in kv_samples:
        kvj = jnp.asarray(kv, jnp.float32)
        a, d, _, _ = _symbolize(kvj, cfg, 0, delta_scale)
        lla_counts += tables.histogram_symbols(np.asarray(a), t_idx, n_t, quant.ANCHOR_ALPHABET)
        lld_counts += tables.histogram_symbols(
            np.asarray(d), t_idx, n_t, quant.lossless_delta_alphabet()
        )
        for lvl in range(1, cfg.n_levels):
            a, d, _, _ = _symbolize(kvj, cfg, lvl, delta_scale)
            if lvl == 1:
                a_counts += tables.histogram_symbols(
                    np.asarray(a), t_idx, n_t, quant.ANCHOR_ALPHABET
                )
            d_counts[lvl] += tables.histogram_symbols(
                np.asarray(d), t_idx, n_t, cfg.delta_alphabet
            )

    def _mk(counts):
        return tables.build_coder_tables(
            tables.normalize_freqs(counts, cfg.precision), cfg.precision
        )

    ct = CodecTables(
        anchor=_mk(a_counts),
        deltas={lvl: _mk(d_counts[lvl]) for lvl in d_counts},
        ll_anchor=_mk(lla_counts),
        ll_delta=_mk(lld_counts),
        table_idx=t_idx,
        delta_scale=delta_scale,
        config=cfg,
        n_layers=L,
        n_channels=C,
    )
    return ensure_stacks(ct)


def _chunk_header(
    cfg: CodecConfig, level: int, T: int, L: int, C: int,
    chunk_idx: Optional[int] = None,
) -> dict:
    """Single source of truth for the chunk bitstream header (wire v1).

    ``chunk_idx`` is the chunk's position in its context (written by the
    KVStore so serving-layer validation can detect a storage server
    returning the *wrong chunk*, not just the wrong level); omitted when
    unknown, keeping standalone encodes byte-identical.
    """
    h = {
        "v": 1,
        "level": int(level),
        "n_tokens": int(T),
        "n_layers": int(L),
        "n_channels": int(C),
        "group_size": int(cfg.group_size),
    }
    if chunk_idx is not None:
        h["chunk_idx"] = int(chunk_idx)
    return h


def peek_chunk_header(blob: bytes) -> dict:
    """Parse only a chunk bitstream's header — O(header), the rANS payload
    is never materialized (``bitstream.peek_header``).

    Serving-layer validation hook: the live ``ServeSession`` checks every
    fetched blob against its plan entry (chosen level, token count, and —
    for store-written blobs, which carry ``chunk_idx`` — chunk identity)
    before spending decode time on it; a storage server returning the wrong
    bitstream must fail loudly, not corrupt the cache silently.
    """
    return bitstream.peek_header(blob)


def verify_chunk(blob: bytes) -> bool:
    """Checksum-gate a chunk bitstream before decode (``bitstream.verify_checksum``).

    Returns ``True`` if the blob carries a valid integrity trailer, ``False``
    for legacy/foreign blobs without one; raises ``bitstream.IntegrityError``
    on corruption.  The serving layer runs this at store read and again on
    every fetched blob so corrupt bytes surface as a retryable failure
    instead of a rANS crash or silent garbage KV.
    """
    return bitstream.verify_checksum(blob)


def encode_chunk(
    kv: np.ndarray | jnp.ndarray, ct: CodecTables, level: int,
    chunk_idx: Optional[int] = None,
) -> bytes:
    """Encode one chunk's KV (L, 2, T, C) at ``level`` into a bitstream."""
    cfg = ct.config
    kv = jnp.asarray(kv, jnp.float32)
    L, two, T, C = kv.shape
    if L != ct.n_layers or C != ct.n_channels:
        raise ValueError(
            f"KV shape {kv.shape} does not match profiled tables "
            f"(L={ct.n_layers}, C={ct.n_channels})"
        )
    a_sym, d_sym, scales, layout = _symbolize(kv, cfg, level, ct.delta_scale)
    a_tab = ct.ll_anchor if level == 0 else ct.anchor
    d_tab = ct.ll_delta if level == 0 else ct.deltas[level]
    t_idx = jnp.asarray(ct.table_idx)
    aw, an, ax = rans.encode(a_sym, t_idx, a_tab)
    dw, dn, dx = rans.encode(d_sym, t_idx, d_tab)
    # level-invariant entries (a.*, scales) lead so they form a contiguous
    # anchor segment in the resumable layout (bitstream.segment_index)
    arrays = {}
    arrays.update(bitstream.pack_stream(np.asarray(aw), np.asarray(an), np.asarray(ax), "a"))
    arrays["scales"] = np.asarray(scales, np.float16)
    arrays.update(bitstream.pack_stream(np.asarray(dw), np.asarray(dn), np.asarray(dx), "d"))
    return bitstream.pack(_chunk_header(cfg, level, T, L, C, chunk_idx), arrays)


def decode_chunk(blob: bytes, ct: CodecTables) -> jnp.ndarray:
    """Decode a chunk bitstream back to KV (L, 2, T, C) float32."""
    cfg = ct.config
    header, arrays = bitstream.unpack(blob)
    level = int(header["level"])
    T = int(header["n_tokens"])
    L = int(header["n_layers"])
    C = int(header["n_channels"])
    layout = gop.make_layout(T, int(header["group_size"]))
    t_idx = jnp.asarray(ct.table_idx)
    a_tab = ct.ll_anchor if level == 0 else ct.anchor
    d_tab = ct.ll_delta if level == 0 else ct.deltas[level]
    aw, an, ax = bitstream.unpack_stream(arrays, "a")
    dw, dn, dx = bitstream.unpack_stream(arrays, "d")
    a_sym = rans.decode(
        jnp.asarray(aw), jnp.asarray(an), jnp.asarray(ax), t_idx, a_tab, layout.n_anchors
    )
    d_sym = rans.decode(
        jnp.asarray(dw), jnp.asarray(dn), jnp.asarray(dx), t_idx, d_tab, layout.n_deltas
    )
    a_sym = _unlanes(a_sym, L, C)
    d_sym = _unlanes(d_sym, L, C)
    scales = jnp.asarray(arrays["scales"].astype(np.float32))
    if level == 0:
        return quant.lossless_reconstruct(a_sym, d_sym, scales, layout)
    anchors = quant.dequantize_anchors(a_sym, scales)
    bins = jnp.asarray(_bins_for_level(cfg, L, level, ct.delta_scale))
    deltas = quant.dequantize_deltas(d_sym, bins, cfg.delta_qmax)
    return gop.merge_anchors_deltas(anchors, deltas, layout)


# ---------------------------------------------------------------------------
# Batched fused decode (serving hot path)
# ---------------------------------------------------------------------------


_CAP_BUCKET = 64  # round padded word caps up: content-dependent stream
# lengths would otherwise retrace the jitted rANS decode per novel cap


def _stack_streams(
    parsed: List[Tuple[dict, Dict[str, np.ndarray]]],
    idxs: Sequence[int],
    prefix: str,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Stack several chunks' packed rANS streams into one padded lane block."""
    streams = [bitstream.unpack_stream(parsed[i][1], prefix) for i in idxs]
    n_lanes = streams[0][0].shape[0]
    cap = max(w.shape[1] for w, _, _ in streams)
    cap = -(-cap // _CAP_BUCKET) * _CAP_BUCKET  # decoder never reads the pad
    words = np.zeros((len(idxs) * n_lanes, cap), np.uint16)
    n_words = np.empty((len(idxs) * n_lanes,), np.int32)
    state = np.empty((len(idxs) * n_lanes,), np.uint32)
    for j, (w, n, x) in enumerate(streams):
        sl = slice(j * n_lanes, (j + 1) * n_lanes)
        words[sl, : w.shape[1]] = w
        n_words[sl] = n
        state[sl] = x
    return words, n_words, state


@functools.partial(
    jax.jit,
    static_argnames=("shape_meta", "out_dtype", "use_pallas", "interpret", "block_groups"),
)
def _assemble_chunks(
    a_sym: jnp.ndarray,  # (N * n_lanes, Gmax) anchor symbols, all chunks
    d_sym: jnp.ndarray,  # (N * n_lanes, Dmax) delta symbols, all chunks
    scales: jnp.ndarray,  # (N, L, 2, Gmax) f32 anchor/group scales
    bins: jnp.ndarray,  # (Nl, L, 2) f32 effective bin widths per lossy chunk
    *,
    shape_meta,  # (L, C, g, qmax, ((T, G, D, is_lossless), ...)) — static
    out_dtype,
    use_pallas: bool,
    interpret: bool,
    block_groups: int,
) -> jnp.ndarray:
    """Reconstruct all chunks' tokens in one traced program: symbol regroup +
    fused dequant kernels + token-major concat.  Returns (L, 2, sum T, C).

    Only geometry and the lossy/lossless partition are static — the lossy
    *level* enters purely as data (``bins``; table offsets were applied in
    the rANS stage), so adaptive per-chunk level choices don't multiply jit
    signatures: one compile per run geometry, not per level pattern.
    """
    from repro.kernels import ref as kref
    from repro.kernels.kvquant import (
        kv_dequant_tokens_pallas,
        kv_lossless_tokens_pallas,
    )

    L, C, g, qmax, chunk_meta = shape_meta
    N = len(chunk_meta)
    Gmax = max(m[1] for m in chunk_meta)
    gm1 = g - 1
    lossy_idx = [i for i, m in enumerate(chunk_meta) if not m[3]]
    ll_idx = [i for i, m in enumerate(chunk_meta) if m[3]]

    # anchors for all chunks: lane-major symbols -> (N, L, 2, Gmax, C)
    a = a_sym.reshape(N, L, 2, C, Gmax).transpose(0, 1, 2, 4, 3)
    d_all = d_sym.reshape(N, L, 2, C, -1)

    def regroup(subset: Sequence[int]) -> jnp.ndarray:
        """Lane-major delta symbols -> (n_sub, L, 2, Gmax, g-1, C).

        The uint16 symbol transpose here replaces the seed path's f32
        ``_unlanes`` transpose at half the bytes; padding appends only
        positions >= the chunk's T (deltas are contiguous in token order).
        """
        outs = []
        for i in subset:
            T, G, D, _ = chunk_meta[i]
            di = d_all[i, ..., :D]
            di = jnp.pad(di, ((0, 0), (0, 0), (0, 0), (0, G * gm1 - D)))
            di = di.reshape(L, 2, C, G, gm1)
            di = jnp.pad(di, ((0, 0), (0, 0), (0, 0), (0, Gmax - G), (0, 0)))
            outs.append(di)
        return jnp.stack(outs).transpose(0, 1, 2, 4, 5, 3)

    tok_by_chunk: Dict[int, jnp.ndarray] = {}

    if lossy_idx:
        sel = jnp.asarray(lossy_idx)
        anchors_f = (a[sel].astype(jnp.float32) - 128.0) * scales[sel][..., None]
        if gm1 == 0:
            tok = anchors_f[:, :, :, :, None, :].astype(out_dtype)
        else:
            d_g = regroup(lossy_idx)  # (Nl, L, 2, Gmax, g-1, C)
            Nl = len(lossy_idx)
            args = (
                d_g.reshape(Nl * L * 2, Gmax, gm1, C),
                anchors_f.reshape(Nl * L * 2, Gmax, C),
                bins.reshape(Nl * L * 2),
            )
            if use_pallas:
                tok = kv_dequant_tokens_pallas(
                    *args,
                    qmax=qmax,
                    out_dtype=out_dtype,
                    interpret=interpret,
                    block_groups=block_groups,
                )
            else:
                tok = kref.kv_dequant_tokens_ref(*args, qmax=qmax, out_dtype=out_dtype)
            tok = tok.reshape(Nl, L, 2, Gmax, g, C)
        for j, i in enumerate(lossy_idx):
            tok_by_chunk[i] = tok[j]

    if ll_idx:
        sel = jnp.asarray(ll_idx)
        a_ll = a[sel]  # uint16 symbols
        s_ll = scales[sel]  # (N0, L, 2, Gmax)
        N0 = len(ll_idx)
        if gm1 == 0:
            tok = (
                (a_ll.astype(jnp.float32) - 128.0) * s_ll[..., None]
            )[:, :, :, :, None, :].astype(out_dtype)
        else:
            d_g = regroup(ll_idx)
            args = (
                d_g.reshape(N0 * L * 2, Gmax, gm1, C),
                a_ll.reshape(N0 * L * 2, Gmax, C),
                s_ll.reshape(N0 * L * 2, Gmax),
            )
            if use_pallas:
                tok = kv_lossless_tokens_pallas(
                    *args,
                    out_dtype=out_dtype,
                    interpret=interpret,
                    block_groups=block_groups,
                )
            else:
                tok = kref.kv_lossless_tokens_ref(*args, out_dtype=out_dtype)
            tok = tok.reshape(N0, L, 2, Gmax, g, C)
        for j, i in enumerate(ll_idx):
            tok_by_chunk[i] = tok[j]

    pieces = []
    for i, (T, G, _, _) in enumerate(chunk_meta):
        tok = tok_by_chunk[i]  # (L, 2, Gmax, g', C)
        gp = tok.shape[3]
        pieces.append(tok[:, :, :G].reshape(L, 2, G * gp, C)[:, :, :T])
    out = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis=2)
    return out.astype(out_dtype)


def _use_pallas_default() -> bool:
    """Kernel choice by platform: the Pallas kernels on accelerators, the
    XLA-fused jnp twins on CPU (where Pallas only runs interpreted)."""
    return jax.default_backend() != "cpu"


def decode_chunks(
    blobs: Sequence[bytes],
    ct: CodecTables,
    *,
    out_dtype=jnp.float32,
    use_pallas: Optional[bool] = None,
    block_groups: int = 8,
) -> jnp.ndarray:
    """Batched fused decode of several chunk bitstreams (serving hot path).

    Parses every blob once on the host, then runs exactly two lane-stacked
    rANS decodes — anchors for all chunks, deltas for all chunks (per-level
    and lossless tables merged via alphabet-padded
    :func:`rans.stack_tables`) — and a single
    jitted assemble step that applies the fused dequant kernels and emits
    token-major KV for all chunks concatenated along the token axis:
    ``(L, 2, sum(T_i), C)`` in ``out_dtype``.  The result stays on device —
    no per-chunk host transfers.

    ``use_pallas=None`` selects the Pallas kernels on accelerator backends
    and the XLA-fused jnp twins on CPU (where Pallas runs in interpret mode
    and is kept as a test oracle, not a fast path).

    Equivalent to concatenating per-chunk :func:`decode_chunk` results:
    bit-exact at level 0 (in f32), tolerance-exact at lossy levels.
    """
    if not blobs:
        raise ValueError("decode_chunks needs at least one blob")
    cfg = ct.config
    with TraceAnnotation("codec.parse", n_chunks=len(blobs)):
        parsed = [bitstream.unpack(b) for b in blobs]
        h0 = parsed[0][0]
        L, C, g = int(h0["n_layers"]), int(h0["n_channels"]), int(h0["group_size"])
        for h, _ in parsed:
            geom = (int(h["n_layers"]), int(h["n_channels"]), int(h["group_size"]))
            if geom != (L, C, g):
                raise ValueError("decode_chunks requires chunks with a common geometry")
        if L != ct.n_layers or C != ct.n_channels:
            raise ValueError(
                f"chunk geometry (L={L}, C={C}) does not match profiled tables "
                f"(L={ct.n_layers}, C={ct.n_channels})"
            )
        metas = []
        for h, _ in parsed:
            lvl, T = int(h["level"]), int(h["n_tokens"])
            layout = gop.make_layout(T, g)
            metas.append((lvl, T, layout.n_anchors, layout.n_deltas))
    if use_pallas is None:
        use_pallas = _use_pallas_default()
    interpret = jax.default_backend() == "cpu"

    N = len(metas)
    n_lanes = L * 2 * C
    Gmax = max(m[2] for m in metas)
    t_idx_np = np.asarray(ct.table_idx)
    n_ta = ct.anchor.n_tables

    with TraceAnnotation("codec.dispatch", n_chunks=N, rans=rans.decoder()):
        # --- anchors: one decode over all chunks (lossy + lossless tables
        # stacked)
        aw, an, ax = _stack_streams(parsed, range(N), "a")
        t_idx_a = np.concatenate(
            [t_idx_np + (n_ta if m[0] == 0 else 0) for m in metas]
        )
        a_sym = rans.decode(aw, an, ax, t_idx_a, _anchor_stack(ct), Gmax)

        # --- deltas: ONE decode for all chunks — lossy levels and the
        # lossless family (different alphabet) share it via alphabet-padded
        # table stacking
        d_max = max(m[3] for m in metas)
        if d_max > 0:
            dw, dn, dx = _stack_streams(parsed, range(N), "d")
            t_idx_d = np.concatenate(
                [t_idx_np + _delta_table_base(ct, m[0]) for m in metas]
            )
            d_sym = rans.decode(dw, dn, dx, t_idx_d, _delta_decode_stack(ct), d_max)
        else:
            d_sym = jnp.zeros((N * n_lanes, 0), jnp.uint16)

        # --- per-chunk side data, padded + stacked once on the host
        lossy_idx = [i for i, m in enumerate(metas) if m[0] != 0]
        scales = np.zeros((N, L, 2, Gmax), np.float32)
        for i, (_, arrays) in enumerate(parsed):
            s = arrays["scales"].astype(np.float32)
            scales[i, :, :, : s.shape[2]] = s
        bins = np.zeros((len(lossy_idx), L, 2), np.float32)
        for j, i in enumerate(lossy_idx):
            bins[j] = _bins_for_level(cfg, L, metas[i][0], ct.delta_scale)

        # static meta carries geometry + the binary lossy/lossless partition
        # only; the chosen lossy level reaches the trace as data (bins)
        shape_meta = (
            L, C, g, cfg.delta_qmax,
            tuple((T, G, D, lvl == 0) for (lvl, T, G, D) in metas),
        )
        return _assemble_chunks(
            a_sym,
            d_sym,
            jnp.asarray(scales),
            jnp.asarray(bins),
            shape_meta=shape_meta,
            out_dtype=np.dtype(out_dtype),
            use_pallas=bool(use_pallas),
            interpret=interpret,
            block_groups=block_groups,
        )


def decode_chunk_runs(
    runs: Sequence[Sequence[bytes]],
    ct: CodecTables,
    *,
    out_dtype=jnp.float32,
    use_pallas: Optional[bool] = None,
    block_groups: int = 8,
    run_tokens: Optional[Sequence[int]] = None,
) -> Tuple[jnp.ndarray, List[Tuple[int, int]]]:
    """Cross-request run assembly: several requests' chunk runs, one decode.

    ``runs`` is one entry per request — that request's consecutive bitstream
    chunks (what a single :func:`decode_chunks` call would take).  All runs
    are flattened into *one* pair of lane-stacked rANS decodes and one jitted
    assemble (``decode_chunks``), so N concurrent requests cost the same
    number of device dispatches as one.  The jit signature is shaped by the
    flattened run geometry (chunk token counts + lossy/lossless split)
    exactly as for a single-request call — request identity (which run a
    chunk came from) never enters the trace; it only determines how the
    caller slices the output.

    Returns ``(kv, spans)``: ``kv`` is the token-major concat
    ``(L, 2, sum_all_T, C)`` of every chunk of every run in order, and
    ``spans[r] = (token_offset, n_tokens)`` locates request ``r``'s run
    inside it.  Slicing ``kv[:, :, off : off + n]`` is bit-identical to the
    request's own ``decode_chunks`` output (the assemble is elementwise per
    chunk; stacking mates cannot perturb it).

    ``run_tokens`` (optional) supplies each run's known token count so the
    span computation skips re-parsing headers the caller already validated
    (the scheduler checks every fetched blob against its plan at fetch
    time); when given it is cross-checked against the decoded total.
    """
    if not runs or any(not r for r in runs):
        raise ValueError("decode_chunk_runs needs non-empty runs")
    flat: List[bytes] = [b for run in runs for b in run]
    kv = decode_chunks(
        flat, ct, out_dtype=out_dtype, use_pallas=use_pallas,
        block_groups=block_groups,
    )
    if run_tokens is None:
        run_tokens = [
            sum(int(peek_chunk_header(b)["n_tokens"]) for b in run)
            for run in runs
        ]
    elif len(run_tokens) != len(runs):
        raise ValueError(
            f"run_tokens covers {len(run_tokens)} runs, got {len(runs)}"
        )
    if sum(run_tokens) != kv.shape[2]:
        raise ValueError(
            f"runs decode to {kv.shape[2]} tokens but run_tokens sums to "
            f"{sum(run_tokens)}; bitstream/plan divergence"
        )
    spans: List[Tuple[int, int]] = []
    off = 0
    for n in run_tokens:
        spans.append((off, int(n)))
        off += int(n)
    return kv, spans


def encode_all_levels(
    kv: np.ndarray | jnp.ndarray, ct: CodecTables,
    chunk_idx: Optional[int] = None,
) -> Dict[int, bytes]:
    """Offline pre-encoding of every streaming level (paper §5.3).

    Batched: the lossy levels share their anchor stream (anchors are
    level-invariant), so anchors are symbolized and entropy-coded exactly
    once, and all lossy levels' delta streams are encoded in one stacked
    rANS call over ``n_lossy_levels * n_lanes`` lanes.  Output bitstreams
    are byte-identical to per-level :func:`encode_chunk`.
    """
    cfg = ct.config
    kv = jnp.asarray(kv, jnp.float32)
    L, two, T, C = kv.shape
    if L != ct.n_layers or C != ct.n_channels:
        raise ValueError(
            f"KV shape {kv.shape} does not match profiled tables "
            f"(L={ct.n_layers}, C={ct.n_channels})"
        )
    out: Dict[int, bytes] = {0: encode_chunk(kv, ct, 0, chunk_idx)}
    lossy = list(range(1, cfg.n_levels))
    if not lossy:
        return out

    layout = gop.make_layout(T, cfg.group_size)
    t_idx = jnp.asarray(ct.table_idx)

    # anchors: level-invariant — symbolize and entropy-code once
    anchors, deltas = gop.split_anchors_deltas(kv, layout)
    a_sym, scales = quant.quantize_anchors(anchors)
    aw, an, ax = rans.encode(_lanes(a_sym), t_idx, ct.anchor)
    a_arrays = bitstream.pack_stream(np.asarray(aw), np.asarray(an), np.asarray(ax), "a")
    scales16 = np.asarray(scales, np.float16)

    # deltas: quantize all levels in one vectorized op, entropy-code in one
    # stacked rANS call (per-lane streams are independent of the stacking)
    bins_all = np.stack(
        [_bins_for_level(cfg, L, lvl, ct.delta_scale) for lvl in lossy]
    )  # (n_lossy, L, 2)
    d_sym_all = quant.quantize_deltas(
        deltas[None], jnp.asarray(bins_all), cfg.delta_qmax
    )  # (n_lossy, L, 2, D, C)
    n_lanes = L * two * C
    d_stack = jnp.transpose(d_sym_all, (0, 1, 2, 4, 3)).reshape(
        len(lossy) * n_lanes, layout.n_deltas
    )
    n_td = ct.deltas[lossy[0]].n_tables
    t_idx_np = np.asarray(ct.table_idx)
    t_stack = np.concatenate([t_idx_np + (lvl - 1) * n_td for lvl in lossy])
    dw, dn, dx = rans.encode(d_stack, jnp.asarray(t_stack), _lossy_delta_stack(ct))
    dw, dn, dx = np.asarray(dw), np.asarray(dn), np.asarray(dx)

    for j, lvl in enumerate(lossy):
        sl = slice(j * n_lanes, (j + 1) * n_lanes)
        arrays = {}
        arrays.update(a_arrays)
        arrays["scales"] = scales16
        arrays.update(bitstream.pack_stream(dw[sl], dn[sl], dx[sl], "d"))
        out[lvl] = bitstream.pack(_chunk_header(cfg, lvl, T, L, C, chunk_idx), arrays)
    return out


def kv_nbytes_fp16(L: int, T: int, C: int) -> int:
    """Baseline 'raw fp16 tensors' wire size for a chunk."""
    return L * 2 * T * C * 2


def kv_nbytes_int8(L: int, T: int, C: int, group_size: int = 10) -> int:
    """Baseline '8-bit uniform quantization' wire size (symbols + scales)."""
    n_groups = -(-T // group_size)
    return L * 2 * T * C + L * 2 * n_groups * 2
