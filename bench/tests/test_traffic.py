"""The request list is a function of the seed, and every seed gets the same
sizes in another order."""
import numpy as np

from bench import traffic

MIX = traffic.load("doc-reuse")


def _waves(seed, n=3):
    rng = np.random.default_rng([seed, 2])
    return [traffic.wave(MIX, rng, 3, 8) for _ in range(n)]


def test_same_seed_same_requests():
    assert _waves(2**31 + 11) == _waves(2**31 + 11)


def test_other_seed_other_order_same_sizes():
    a, b = _waves(1), _waves(2)
    assert a != b
    for wa, wb in zip(a, b):
        for key in ("doc", "n_out"):
            assert sorted(r[key] for r in wa) == sorted(r[key] for r in wb)
        assert sorted(sorted(r["gbps"]) for r in wa) == sorted(sorted(r["gbps"]) for r in wb)


def test_sizes():
    assert traffic.doc_chunks(MIX, 3) == [2, 4, 7]
    # Zipf 1, 1/2, 1/3 of 8: 4.36, 2.18, 1.45 -> 4, 2, 1 and the remainder to the third
    assert traffic.requests_per_doc(MIX, 3, 8) == [4, 2, 2]
    outs = traffic.output_lengths(MIX, 8)
    assert outs[0] == MIX["output_tokens"][0] and outs[-1] == MIX["output_tokens"][1]


def test_documents_follow_the_seed():
    a = traffic.document_tokens(np.random.default_rng(5), 512, 49152, 1.2)
    b = traffic.document_tokens(np.random.default_rng(5), 512, 49152, 1.2)
    c = traffic.document_tokens(np.random.default_rng(6), 512, 49152, 1.2)
    assert (a == b).all() and (a != c).any()
    assert a.min() >= 0 and a.max() < 49152
