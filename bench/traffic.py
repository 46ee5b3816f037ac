"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<name>.json`` and makes documents and waves from a seed.

Every seed gets the same set of sizes: document lengths at fixed quantiles
of the mix's distribution, the same count of requests per document in each
wave, output lengths evenly spread over the mix's range, and link segments
from one fixed grid of bandwidths.  The seed changes the token content and
the order: which request gets which document, output length and link.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def doc_chunks(mix: dict, n_docs: int) -> List[int]:
    """Document lengths in chunks: ``n_docs`` quantiles of the mix's
    log-uniform range, shortest first."""
    lo, hi = mix["doc_chunks"]
    if n_docs == 1:
        return [int(round(np.sqrt(lo * hi)))]
    q = np.exp(np.linspace(np.log(lo), np.log(hi), n_docs))
    return [int(round(x)) for x in q]


def document_tokens(rng: np.random.Generator, n_tokens: int, vocab: int,
                    zipf_a: float) -> np.ndarray:
    """Token ids with Zipf-distributed frequencies (as words in text) over a
    seeded ranking of the vocabulary."""
    ranking = rng.permutation(vocab)
    ranks = np.minimum(rng.zipf(zipf_a, size=n_tokens) - 1, vocab - 1)
    return ranking[ranks].astype(np.int32)


def requests_per_doc(mix: dict, n_docs: int, clients: int) -> List[int]:
    """Requests per document in each wave: Zipf popularity over documents
    (the first document most popular), rounded by largest remainder so that
    they sum to ``clients``."""
    w = 1.0 / np.arange(1, n_docs + 1) ** float(mix["popularity_zipf_s"])
    share = w / w.sum() * clients
    counts = np.floor(share).astype(int)
    for i in np.argsort(-(share - counts))[: clients - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def output_lengths(mix: dict, clients: int) -> List[int]:
    lo, hi = mix["output_tokens"]
    return [int(round(x)) for x in np.linspace(lo, hi, clients)]


def link_grid(mix: dict) -> np.ndarray:
    link = mix["link"]
    lo, hi = link["gbps"]
    return np.exp(np.linspace(np.log(lo), np.log(hi), link["segments"]))


def wave(mix: dict, rng: np.random.Generator, n_docs: int,
         clients: int) -> List[Dict]:
    """One wave of ``clients`` requests: each names a document, an output
    length and a link (segment start times and Gbit/s per segment)."""
    docs = np.repeat(np.arange(n_docs), requests_per_doc(mix, n_docs, clients))
    docs = rng.permutation(docs)
    outs = rng.permutation(output_lengths(mix, clients))
    grid = link_grid(mix)
    seg = float(mix["link"]["segment_s"])
    out = []
    for doc, n_out in zip(docs, outs):
        gbps = rng.permutation(grid)
        out.append(dict(
            doc=int(doc), n_out=int(n_out),
            times=(np.arange(len(gbps)) * seg).tolist(), gbps=gbps.tolist(),
        ))
    return out
