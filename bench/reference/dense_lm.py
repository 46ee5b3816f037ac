"""Plain reference of a dense decoder LM and of CacheGen's KV quantization.

Written from the published descriptions, in ``jax.numpy`` and float32 at
``highest`` matmul precision, with no kernels, cache or batching, and with
nothing imported from the system under test.  The benchmark also makes the
served weights here, so the reference never takes them from the program.

Model (Llama / SmolLM and OLMo style): token embedding tied with the output
head; per layer a pre-norm attention block (RoPE on the first and second
half of each head, grouped-query attention, causal softmax scaled by
``1/sqrt(d_head)``) and a pre-norm SwiGLU MLP.  The norm is RMSNorm with a
gain (SmolLM, eps 1e-5) or a layer norm with no parameters (OLMo, eps 1e-5).
The program's RMSNorm uses eps 1e-6; on activations of unit scale the two
differ far below the bf16 rounding of the served path.

CacheGen's stored KV (arXiv:2310.07240, section 5.2), per 256-token chunk
and groups of ``group_size`` tokens, whose first token is the anchor:

* level 0: each (layer, K/V, group) is quantized to 8 bits with one scale,
  ``absmax / 127`` rounded to float16;
* lossy level ``l``: anchors get 8-bit vectorwise scales (per anchor token,
  ``absmax / 127`` in float16), and every other token's difference from its
  raw anchor is rounded to bins of ``layer_group_bin * level_mult[l-1] *
  delta_scale[layer, kv]`` (clipped to +-qmax), where ``delta_scale`` is the
  root mean square of those differences over the calibration sample.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    return dict(
        L=cfg["num_hidden_layers"], d=d, hq=hq, hkv=cfg["num_key_value_heads"],
        dh=cfg.get("head_dim", d // hq), ff=cfg["intermediate_size"],
        V=cfg["vocab_size"],
    )


def _dims(cfg: dict) -> dict:
    return dict(_sizes(cfg), theta=float(cfg["rope_theta"]), norm=cfg["norm"],
                eps=float(cfg["norm_eps"]))


def program_fields(cfg: dict) -> dict:
    """The program's ``ArchConfig`` fields that the configuration implies."""
    m = _sizes(cfg)
    return dict(
        n_layers=m["L"], d_model=m["d"], n_heads=m["hq"], n_kv_heads=m["hkv"],
        d_head=m["dh"], d_ff=m["ff"], vocab_size=m["V"],
        tie_embeddings=cfg["tie_word_embeddings"], norm=cfg["norm"],
        rope_theta=float(cfg["rope_theta"]),
    )


def param_shapes(cfg: dict) -> Dict:
    """Parameter tree as the serving engine takes it (layers stacked)."""
    m = _dims(cfg)
    L, d, hq, hkv, dh, ff = m["L"], m["d"], m["hq"], m["hkv"], m["dh"], m["ff"]
    norm = {"gamma": (d,)} if m["norm"] == "rmsnorm" else {}
    layer_norm = {"gamma": (L, d)} if m["norm"] == "rmsnorm" else {}
    return {
        "embed": (m["V"], d),
        "final_norm": norm,
        "layers": {
            "ln1": dict(layer_norm),
            "ln2": dict(layer_norm),
            "attn": {
                "wq": (L, d, hq * dh), "wk": (L, d, hkv * dh),
                "wv": (L, d, hkv * dh), "wo": (L, hq * dh, d),
            },
            "mlp": {"w_gate": (L, d, ff), "w_up": (L, d, ff), "w_down": (L, ff, d)},
        },
    }


def make_key(seed: int):
    """A PRNG key from any non-negative seed (64 bits are kept)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0xFFFFFFFF), (seed >> 32) & 0xFFFFFFFF
    )


def init_params(cfg: dict, key, dtype=jnp.bfloat16):
    """Random weights in the served dtype: gains 1, the embedding
    N(0, embed_std), every matrix N(0, 1/fan_in), the query and key
    projections scaled by ``qk_gain`` so that attention is peaked and the
    tokens depend on the cached context (``cfg["random_init"]``).  Call
    under ``jax.jit``: one program."""
    init = cfg["random_init"]
    shapes = param_shapes(cfg)
    flat, tree = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
    )
    paths = [
        jax.tree_util.keystr(p)
        for p, _ in jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=lambda x: isinstance(x, tuple)
        )[0]
    ]
    keys = jax.random.split(key, len(flat))
    vals = []
    for path, shape, k in zip(paths, flat, keys):
        if "gamma" in path:
            vals.append(jnp.ones(shape, dtype))
            continue
        std = init["embed_std"] if path == "['embed']" else 1.0 / math.sqrt(shape[-2])
        if path.endswith("['wq']") or path.endswith("['wk']"):
            std *= init["qk_gain"]
        vals.append((jax.random.normal(k, shape, jnp.float32) * std).astype(dtype))
    return jax.tree_util.tree_unflatten(tree, vals)


def _norm(m, x, gamma):
    if m["norm"] == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + m["eps"])
        return y * gamma
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + m["eps"])


def _rope(m, x, pos):
    """x (T, H, dh), pos (T,): rotate the first half against the second."""
    half = m["dh"] // 2
    inv = m["theta"] ** (-np.arange(half, dtype=np.float64) / half)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


# How a forward pass rounds: ``(operands, stored)``, each a dtype name or
# None for float32.  ``operands`` rounds every activation that enters a
# matmul (as an fp8 GEMM takes its inputs); ``stored`` rounds every value a
# serving program holds between operations (matmul outputs, the residual
# stream, K and V, the logits), as a program that computes in bfloat16 does.
# The plain reference is ``F32``.
F32 = (None, None)


def _cast(x, dtype):
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


def _layer(m, p, x, pos, k_prev, v_prev, n_prev, nx=F32):
    """One block over new tokens ``x`` (T, d) at positions ``pos``, attending
    to ``n_prev`` earlier tokens (``k_prev``/``v_prev`` (S, hkv, dh), rows
    past ``n_prev`` ignored) and causally to themselves.  Returns the new
    hidden states and the new tokens' (RoPE'd) K and V."""
    A = functools.partial(_cast, dtype=nx[0])
    St = functools.partial(_cast, dtype=nx[1])
    T = x.shape[0]
    hq, hkv, dh = m["hq"], m["hkv"], m["dh"]
    gamma1 = p["ln1"].get("gamma")
    h = A(St(_norm(m, x, gamma1)))
    q = St(_rope(m, St(_mm(h, p["attn"]["wq"])).reshape(T, hq, dh), pos))
    k = St(_rope(m, St(_mm(h, p["attn"]["wk"])).reshape(T, hkv, dh), pos))
    v = St(_mm(h, p["attn"]["wv"]).reshape(T, hkv, dh))
    keys = jnp.concatenate([k_prev, k], 0)
    vals = jnp.concatenate([v_prev, v], 0)
    S = k_prev.shape[0]
    rep = hq // hkv
    kh = A(jnp.repeat(keys, rep, axis=1))
    vh = A(jnp.repeat(vals, rep, axis=1))
    s = St(jnp.einsum("qhd,khd->hqk", A(q), kh, precision=HIGHEST)) / math.sqrt(dh)
    kpos = jnp.arange(S + T)
    prev_ok = (kpos < n_prev)[None, :] & (kpos < S)[None, :]
    self_ok = (kpos[None, :] - S <= jnp.arange(T)[:, None]) & (kpos >= S)[None, :]
    s = jnp.where((prev_ok | self_ok)[None], s, -jnp.inf)
    w = A(St(jax.nn.softmax(s, axis=-1)))
    o = St(jnp.einsum("hqk,khd->qhd", w, vh, precision=HIGHEST).reshape(T, hq * dh))
    x = St(x + St(_mm(A(o), p["attn"]["wo"])))
    h2 = A(St(_norm(m, x, p["ln2"].get("gamma"))))
    g = St(_mm(h2, p["mlp"]["w_gate"]))
    u = St(_mm(h2, p["mlp"]["w_up"]))
    x = St(x + St(_mm(A(St(jax.nn.silu(g) * u)), p["mlp"]["w_down"])))
    return x, k, v


def _logits(m, params, x, nx=F32):
    h = _cast(_cast(_norm(m, x, params["final_norm"].get("gamma")), nx[1]), nx[0])
    return _cast(_mm(h, params["embed"].T), nx[1])


def _f32(params):
    return jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), params)


@functools.partial(jax.jit, static_argnames=("cfg_items", "numerics"))
def prefill(params, tokens, *, cfg_items, numerics=F32):
    """Full forward over ``tokens`` (T,): last-position logits (V,) and the
    per-layer K, V (L, T, hkv, dh)."""
    m = _dims(dict(cfg_items))
    params = _f32(params)
    T = tokens.shape[0]
    x = params["embed"][tokens]
    pos = jnp.arange(T)
    empty = jnp.zeros((0, m["hkv"], m["dh"]), jnp.float32)

    def body(x, p):
        x, k, v = _layer(m, p, x, pos, empty, empty, 0, numerics)
        return x, (k, v)

    x, (k, v) = jax.lax.scan(body, x, params["layers"])
    return _logits(m, params, x[-1:], numerics)[0], k, v


@functools.partial(jax.jit, static_argnames=("cfg_items", "numerics"))
def continue_(params, k_doc, v_doc, tokens, *, cfg_items, numerics=F32):
    """Forward over ``tokens`` (n,) placed after the document whose per-layer
    K, V are ``k_doc``/``v_doc`` (L, T, hkv, dh): logits (n, V)."""
    m = _dims(dict(cfg_items))
    params = _f32(params)
    T = k_doc.shape[1]
    n = tokens.shape[0]
    x = params["embed"][tokens]
    pos = T + jnp.arange(n)

    def body(x, inp):
        p, kd, vd = inp
        x, _, _ = _layer(m, p, x, pos, kd, vd, T, numerics)
        return x, None

    x, _ = jax.lax.scan(body, x, (params["layers"], k_doc, v_doc))
    return _logits(m, params, x, numerics)


# ---------------------------------------------------------------------------
# CacheGen's stored KV
# ---------------------------------------------------------------------------


def _f16(x):
    return x.astype(jnp.float16).astype(jnp.float32)


def _groups(n_tokens: int, g: int) -> np.ndarray:
    return np.arange(n_tokens) // g


def quantize_lossless(x, g: int):
    """Level 0 of one chunk ``x`` (L, 2, T, C): 8 bits, one scale per group."""
    L, two, T, _ = x.shape
    n_groups = -(-T // g)
    absmax = jnp.max(jnp.abs(x), axis=-1)  # (L, 2, T)
    padded = jnp.pad(absmax, ((0, 0), (0, 0), (0, n_groups * g - T)))
    gmax = jnp.max(padded.reshape(L, two, n_groups, g), -1)
    scale = _f16(jnp.maximum(gmax / 127.0, 1e-7))[..., _groups(T, g)][..., None]
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def deltas_of(x, g: int):
    """Differences of every non-anchor token from its group's raw anchor."""
    T = x.shape[2]
    pos = np.arange(T)
    anchor = (pos // g) * g
    return (x - x[:, :, anchor])[:, :, pos % g != 0]


def quantize_lossy(x, g: int, bins, qmax: int):
    """A lossy level of one chunk ``x`` (L, 2, T, C); ``bins`` (L, 2)."""
    T = x.shape[2]
    pos = np.arange(T)
    anchor_pos = pos[pos % g == 0]
    anchors = x[:, :, anchor_pos]
    a_scale = _f16(jnp.maximum(jnp.max(jnp.abs(anchors), -1) / 127.0, 1e-7))[..., None]
    a_hat = jnp.clip(jnp.round(anchors / a_scale), -127, 127) * a_scale
    b = jnp.asarray(bins, jnp.float32)[:, :, None, None]
    d = x - x[:, :, (pos // g) * g]
    d_hat = jnp.clip(jnp.round(d / b), -qmax, qmax) * b  # 0 at anchors
    return a_hat[:, :, pos // g] + d_hat


def layer_group_bins(n_layers: int, group_bins: Sequence[float]) -> np.ndarray:
    """Bin of each layer: layers split into len(group_bins) equal groups."""
    edges = np.linspace(0, n_layers, len(group_bins) + 1)
    gid = np.searchsorted(edges[1:-1], np.arange(n_layers), side="right")
    return np.asarray(group_bins, np.float32)[gid]


def delta_scale(kv, g: int):
    """Root mean square of the anchor differences per (layer, K/V) over a
    calibration sample ``kv`` (L, 2, T, C)."""
    d = deltas_of(kv, g)
    return jnp.maximum(jnp.sqrt(jnp.mean(d * d, axis=(2, 3))), 1e-6)


@functools.partial(jax.jit, static_argnames=("levels", "codec_items"))
def stored_kv(k, v, dscale, *, levels, codec_items):
    """What the store holds for a document at the chunks' ``levels``:
    K, V (L, T, hkv, dh) -> their dequantized values, same shape."""
    codec = dict(codec_items)
    L, T, hkv, dh = k.shape
    kv = jnp.stack([k.reshape(L, T, -1), v.reshape(L, T, -1)], 1)
    g, ct = codec["group_size"], codec["chunk_tokens"]
    base = layer_group_bins(L, codec["layer_group_bins"])
    out = []
    for i, lvl in enumerate(levels):
        x = kv[:, :, i * ct:(i + 1) * ct]
        if lvl == 0:
            out.append(quantize_lossless(x, g))
        else:
            bins = base[:, None] * np.float32(codec["level_mults"][lvl - 1]) * dscale
            out.append(quantize_lossy(x, g, bins, codec["delta_qmax"]))
    kv = jnp.concatenate(out, 2)
    return kv[:, 0].reshape(L, T, hkv, dh), kv[:, 1].reshape(L, T, hkv, dh)


def _items(d: dict) -> tuple:
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v) for k, v in d.items()
        if isinstance(v, (int, float, str, list))
    ))


def served_gaps(params, cfg: dict, requests: List[dict], codec: dict,
                calib_tokens, *, max_tokens: int,
                sides: Optional[Dict[str, tuple]] = None) -> Dict[str, float]:
    """How far the served tokens lie below the reference's best, over
    ``requests``: per served token, the reference's best logit less its
    logit for that token.  Returns the widest such gap (``gap``), the mean
    over every served token (``gap_mean``), the mean of its square
    (``gap_sq_mean``) and the share of served tokens that are not the
    reference's first choice (``flip_share``).

    Each request is ``{"doc": (T,) tokens, "levels": per-chunk levels,
    "tokens": served tokens, the prefill's greedy token first}``.  Each entry
    ``name: (weights dtype, numerics)`` of ``sides`` runs the same prompts
    and tokens with the weights rounded to that dtype and computed with those
    numerics (see ``F32``); the ``<name>_*`` numbers are the reference's gaps
    of the tokens that this side puts first.  Continuations are padded to
    ``max_tokens`` so that one program serves every request.
    """
    items = _items(cfg)
    codec_items = _items(codec)
    # a side's weights are materialized in its dtype: a rounding inside the
    # jitted forward could be folded away by the compiler
    runs = [("", params, F32)] + [
        (name + "_", jax.tree_util.tree_map(lambda w, d=wd: w.astype(d), params),
         tuple(nx))
        for name, (wd, nx) in (sides or {}).items()]
    gaps: Dict[str, list] = {name: [] for name, _, _ in runs}
    with jax.default_matmul_precision("highest"):
        _, ck, cv = prefill(params, jnp.asarray(calib_tokens), cfg_items=items)
        L, T, hkv, dh = ck.shape
        calib = jnp.stack([ck.reshape(L, T, -1), cv.reshape(L, T, -1)], 1)
        dscale = jax.jit(delta_scale, static_argnums=1)(calib, codec["group_size"])
        del ck, cv, calib
        for r in requests:
            served = np.asarray(r["tokens"], np.int32)
            ref_logits = None
            for name, weights, nx in runs:
                last, k, v = prefill(weights, jnp.asarray(r["doc"]), cfg_items=items,
                                     numerics=nx)
                k, v = stored_kv(k, v, dscale, levels=tuple(r["levels"]),
                                 codec_items=codec_items)
                k, v = _cast(k, nx[1]), _cast(v, nx[1])
                cont_in = np.zeros((max_tokens,), np.int32)
                cont_in[: len(served) - 1] = served[:-1]
                cont = continue_(weights, k, v, jnp.asarray(cont_in), cfg_items=items,
                                 numerics=nx)
                logits = np.concatenate(
                    [np.asarray(last)[None],
                     np.asarray(cont)[: len(served) - 1]], 0
                ).astype(np.float64)
                if ref_logits is None:
                    ref_logits = logits
                    picked = served
                else:
                    picked = logits.argmax(-1)
                rows = np.arange(len(picked))
                gaps[name].append(ref_logits.max(-1) - ref_logits[rows, picked])
    out = {}
    for name, g in gaps.items():
        g = np.concatenate(g)
        out[name + "gap"] = float(g.max())
        out[name + "gap_mean"] = float(g.mean())
        out[name + "gap_sq_mean"] = float(np.mean(g * g))
        out[name + "flip_share"] = float(np.mean(g > 0))
    return out


# ---------------------------------------------------------------------------
# The least work of the served path (``bench/costs.py``)
# ---------------------------------------------------------------------------

BF16 = 2


def n_params(cfg: dict) -> int:
    """Weights of the served model (tied embedding counted once; norm gains
    included)."""
    m = _sizes(cfg)
    per_layer = (
        m["d"] * m["hq"] * m["dh"] * 2  # wq, wo
        + m["d"] * m["hkv"] * m["dh"] * 2  # wk, wv
        + 3 * m["d"] * m["ff"]  # gate, up, down
    )
    gains = (2 * m["L"] + 1) * m["d"] if cfg["norm"] == "rmsnorm" else 0
    return m["L"] * per_layer + m["V"] * m["d"] + gains


def kv_bytes_per_token(cfg: dict) -> int:
    """bf16 K and V of one token over all layers."""
    m = _sizes(cfg)
    return m["L"] * 2 * m["hkv"] * m["dh"] * BF16


def decode_step(cfg: dict, lengths: Iterable[int]) -> dict:
    """One decode step for the active rows, each holding ``length`` tokens
    before the step: the least FLOPs and HBM bytes.

    FLOPs: 2 per weight per row for the layer matmuls and the output head,
    plus 2 x 2 per (query head, head dim, attended position) per layer.
    Bytes: every weight read once, each active row's cached K and V read
    once, its new token's K and V written once.
    """
    m = _sizes(cfg)
    lengths = [int(n) for n in lengths]
    rows = len(lengths)
    matmul = n_params(cfg) - ((2 * m["L"] + 1) * m["d"] if cfg["norm"] == "rmsnorm" else 0)
    attended = sum(n + 1 for n in lengths)
    flops = 2 * matmul * rows + 4 * m["hq"] * m["dh"] * attended * m["L"]
    per_tok = kv_bytes_per_token(cfg)
    nbytes = n_params(cfg) * BF16 + per_tok * sum(lengths) + per_tok * rows
    return dict(flops=float(flops), bytes=float(nbytes))


def token_block(cfg: dict):
    """(layer, K/V) rows and channels of one stored chunk."""
    m = _sizes(cfg)
    return m["L"] * 2, m["hkv"] * m["dh"]
