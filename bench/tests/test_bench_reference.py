"""The plain reference against independent implementations (CPU)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from corpus import CorpusConfig, SyntheticCorpus
from reference import replay, signatures

TAU_B = 0.7 / (2.0 - 0.7)


def _stream(n: int = 96):
    cfg = CorpusConfig(dup_rate=0.4, mean_len=40, max_len=64, min_len=3,
                       window=32, seed=2**31 + 3)
    tokens, lengths, dup_of = SyntheticCorpus(cfg).next_batch(n)
    return tokens, lengths, dup_of


def _ref_bitmaps(tokens, lengths):
    seeds = signatures.seeds(112, 0)
    return np.asarray(signatures.doc_bitmaps(
        jnp.asarray(tokens), jnp.asarray(lengths), seeds, n=5, T=4096))


def test_bitmaps_equal_the_programs_signature_stage():
    from repro.core import bitmap
    from repro.core.hashing import hash_seeds
    from repro.core.minhash import minhash_signatures
    tokens, lengths, _ = _stream(48)
    sigs = minhash_signatures(jnp.asarray(tokens), jnp.asarray(lengths),
                              hash_seeds(112, 0), n=5)
    want = np.asarray(bitmap.pack_bitmaps(sigs, T=4096))
    np.testing.assert_array_equal(_ref_bitmaps(tokens, lengths), want)


def test_one_document_batches_equal_online_admission():
    """Doc-by-doc replay == the oracle's sequential admission over the
    pairwise bitmap-Jaccard matrix at the same bitmap threshold."""
    from repro.core.bitmap import pairwise_bitmap_jaccard
    from repro.core.oracle import online_admission
    tokens, lengths, dup_of = _stream()
    bm = _ref_bitmaps(tokens, lengths)
    sim = np.asarray(pairwise_bitmap_jaccard(jnp.asarray(bm),
                                             jnp.asarray(bm)))
    want, _ = online_admission(sim, np.float32(TAU_B))
    got, kept = replay.replay(bm, [np.array([d]) for d in range(len(bm))],
                              TAU_B)
    np.testing.assert_array_equal(got, want)
    assert kept.all()                      # a batch of one keeps its row
    assert 0 < (~got).sum() <= (dup_of >= 0).sum() + 2


def _bits(*positions):
    row = np.zeros(128, np.uint32)
    for p in positions:
        row[p // 32] |= np.uint32(1) << np.uint32(p % 32)
    return row


def test_multi_document_batch_hand_worked():
    """Batch 1 = [a, a', b, a''], batch 2 = [c, b']: worked by hand.

    a and a' share 9 of 10 bits each (J = 9/11 >= 7/13), a'' shares 9
    with a' only (J(a, a'') = 8/12 = 0.67 >= 0.538 too). b is disjoint.
    Greedy in row order keeps a, drops a' (a), keeps b, drops a'' (a).
    Batch 2: c is new; b' = b with one bit moved (J = 9/11) is an index
    duplicate of b."""
    a = _bits(*range(0, 10))
    a1 = _bits(*range(0, 9), 50)
    a2 = _bits(*range(1, 9), 50, 51)
    b = _bits(*range(100, 110))
    c = _bits(*range(200, 210))
    b1 = _bits(*range(100, 109), 300)
    bm = np.stack([a, a1, b, a2, c, b1])
    s = replay.pair_sims(bm[:, None, :], bm[None, :, :])
    assert s[0, 1] == np.float32(18 / 22) and s[0, 3] == np.float32(16 / 24)
    adm, kept = replay.replay(bm, [np.array([0, 1, 2, 3]),
                                   np.array([4, 5])], TAU_B)
    np.testing.assert_array_equal(adm, [1, 0, 1, 0, 1, 0])
    np.testing.assert_array_equal(kept, [1, 0, 1, 0, 1, 1])


def test_threshold_table_is_the_float32_cut():
    thr = replay.threshold_table(TAU_B, 448)
    t = np.float32(TAU_B)
    for u in range(1, 449):
        i = thr[u]
        assert np.float32(i) / np.float32(u) >= t
        assert i == 0 or np.float32(i - 1) / np.float32(u) < t


_CUT = np.float32(TAU_B)
_TIE = np.float32(7 / 13)                   # == _CUT: an exact tie


@pytest.mark.parametrize("kept, s01, s02, s12, breaks", [
    ([1, 0, 1], 0.9, 0.1, 0.1, 0),          # the greedy leader's answer
    ([1, 1, 1], 0.9, 0.1, 0.1, 1),          # kept under a kept duplicate
    ([1, 0, 0], 0.9, 0.1, 0.1, 1),          # dropped with no duplicate
    ([1, 1, 1], _TIE, 0.1, 0.1, 0),         # a tie may go either way
    ([1, 0, 1], _TIE, 0.1, 0.1, 0),
    ([1, 0, 0], 0.9, 0.1, 0.9, 1),          # its duplicate was dropped
])
def test_greedy_breaks_counts_rule_breaks(kept, s01, s02, s12, breaks):
    from reference.compare import _greedy_breaks
    sims = np.eye(3, dtype=np.float32)
    sims[0, 1] = sims[1, 0] = s01
    sims[0, 2] = sims[2, 0] = s02
    sims[1, 2] = sims[2, 1] = s12
    assert _TIE == _CUT
    assert _greedy_breaks(np.array(kept, bool), sims, _CUT) == breaks
