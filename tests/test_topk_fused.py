"""Fused streaming top-k kernel vs the ``topk_search`` oracle.

Property tests (hypothesis; the conftest shim when the package is absent)
over ragged Q/R/W shapes, duplicate-score tie-breaking, k >= R edges, and
the shard-masking contract — all in interpret mode (tier-1, CPU). The
real-mesh fused path runs in the slow tier of tests/test_serve.py.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.hd.similarity import (
    bitpack_bipolar,
    topk_search,
    topk_search_packed,
)
from repro.kernels.topk_hamming import topk_hamming_pallas
from repro.kernels.topk_hamming.ref import topk_hamming_ref
from repro.serve import search_with_fdr, shard_database, sharded_topk_search

_SENTINEL = np.iinfo(np.int32).min


def _bipolar(rng, shape):
    return jnp.asarray(rng.choice([-1, 1], size=shape).astype(np.int8))


def _assert_same(got, want, *ctx):
    gi, gv = got
    wi, wv = want
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi), err_msg=str(ctx))
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv), err_msg=str(ctx))


# --------------------------------------------------------------------------
# property tests vs the materialize-then-top_k oracle
# --------------------------------------------------------------------------

class TestFusedVsOracleProperties:
    @settings(max_examples=12)
    @given(st.integers(1, 33), st.integers(1, 200), st.integers(1, 7),
           st.integers(1, 9))
    def test_packed_random_shapes(self, q, r, w, k):
        k = min(k, r)
        rng = np.random.default_rng(q * 7919 + r * 131 + w * 17 + k)
        qp = jnp.asarray(rng.integers(0, 2**32, (q, w), dtype=np.uint32))
        rp = jnp.asarray(rng.integers(0, 2**32, (r, w), dtype=np.uint32))
        got = topk_hamming_pallas(qp, rp, dim=w * 32, k=k, block_r=128)
        want = topk_hamming_ref(qp, rp, w * 32, k)
        _assert_same(got, want, q, r, w, k)

    @settings(max_examples=10)
    @given(st.integers(1, 17), st.integers(1, 90), st.integers(1, 100),
           st.integers(1, 8))
    def test_int8_dot_random_shapes(self, q, r, d, k):
        """The unpacked int8-dot variant (the D % 32 != 0 fallback) against
        the plain topk_search oracle."""
        k = min(k, r)
        rng = np.random.default_rng(q * 733 + r * 37 + d * 5 + k)
        qs = _bipolar(rng, (q, d))
        rs = _bipolar(rng, (r, d))
        got = topk_hamming_pallas(qs, rs, dim=d, k=k)
        want = topk_search(qs, rs, k)
        _assert_same(got, want, q, r, d, k)

    @settings(max_examples=10)
    @given(st.integers(2, 40), st.integers(1, 6))
    def test_duplicate_scores_tiebreak(self, r, k):
        """Duplicated reference rows force exact score ties everywhere; the
        streaming merge must order them by ascending index like lax.top_k."""
        k = min(k, 3 * r)
        rng = np.random.default_rng(r * 101 + k)
        base = _bipolar(rng, (r, 32))
        refs = jnp.concatenate([base, base, base], axis=0)
        queries = base[: min(r, 8)]
        got = topk_hamming_pallas(bitpack_bipolar(queries),
                                  bitpack_bipolar(refs), dim=32, k=k,
                                  block_r=128)
        want = topk_search(queries, refs, k)
        _assert_same(got, want, r, k)


# --------------------------------------------------------------------------
# edges: k >= R, masking, block invariance
# --------------------------------------------------------------------------

class TestFusedEdges:
    def test_k_equals_r(self):
        rng = np.random.default_rng(0)
        refs = _bipolar(rng, (9, 64))
        queries = _bipolar(rng, (4, 64))
        got = topk_hamming_pallas(bitpack_bipolar(queries),
                                  bitpack_bipolar(refs), dim=64, k=9)
        want = topk_search(queries, refs, 9)
        _assert_same(got, want)

    def test_k_exceeding_r_raises(self):
        rng = np.random.default_rng(1)
        qp = jnp.asarray(rng.integers(0, 2**32, (2, 2), dtype=np.uint32))
        rp = jnp.asarray(rng.integers(0, 2**32, (5, 2), dtype=np.uint32))
        with pytest.raises(ValueError, match="k="):
            topk_hamming_pallas(qp, rp, dim=64, k=6)

    @pytest.mark.parametrize("num_valid", [0, 1, 3, 7, 10])
    def test_num_valid_masks_like_local_topk(self, num_valid):
        """Rows >= num_valid must behave exactly like the sentinel-masked
        padding columns of db_search._local_topk: sentinel scores, and the
        overflow slots fill with ascending masked indices."""
        rng = np.random.default_rng(2)
        refs = _bipolar(rng, (10, 32))
        queries = _bipolar(rng, (5, 32))
        k = 6
        got = topk_hamming_pallas(bitpack_bipolar(queries),
                                  bitpack_bipolar(refs), dim=32, k=k,
                                  num_valid=num_valid)
        want = topk_hamming_ref(bitpack_bipolar(queries),
                                bitpack_bipolar(refs), 32, k,
                                num_valid=num_valid)
        _assert_same(got, want, num_valid)
        if num_valid < k:
            # overflow slots carry the sentinel at the lowest masked rows
            gi, gv = got
            assert (np.asarray(gv)[:, num_valid:] == _SENTINEL).all()
            np.testing.assert_array_equal(
                np.asarray(gi)[:, num_valid:],
                np.broadcast_to(np.arange(num_valid, k),
                                (5, k - num_valid)))

    def test_block_shape_invariance(self):
        rng = np.random.default_rng(3)
        qp = jnp.asarray(rng.integers(0, 2**32, (10, 4), dtype=np.uint32))
        rp = jnp.asarray(rng.integers(0, 2**32, (300, 4), dtype=np.uint32))
        a = topk_hamming_pallas(qp, rp, dim=128, k=5, block_q=8, block_r=64)
        b = topk_hamming_pallas(qp, rp, dim=128, k=5, block_q=128,
                                block_r=128)
        _assert_same(a, b)

    def test_word_padding_is_harmless(self):
        """W wider than one word_chunk but not a multiple of it pads with
        zero words on both operands (XOR -> 0 -> popcount 0)."""
        rng = np.random.default_rng(4)
        qp = jnp.asarray(rng.integers(0, 2**32, (6, 130), dtype=np.uint32))
        rp = jnp.asarray(rng.integers(0, 2**32, (40, 130), dtype=np.uint32))
        got = topk_hamming_pallas(qp, rp, dim=130 * 32, k=4, word_chunk=128)
        want = topk_hamming_ref(qp, rp, 130 * 32, 4)
        _assert_same(got, want)


# --------------------------------------------------------------------------
# serving integration: fused == unfused == oracle through the shard merge
# --------------------------------------------------------------------------

class TestFusedServingPath:
    @pytest.mark.parametrize("num_shards", [2, 4, 8])
    @pytest.mark.parametrize("num_refs,dim", [
        (61, 32),   # ragged last shard at every shard count, tie-heavy low D
        (64, 64),   # exact split
        (37, 48),   # D % 32 != 0 -> int8-dot kernel variant
    ])
    def test_fused_sharded_topk_matches_oracle(self, num_shards, num_refs,
                                               dim):
        rng = np.random.default_rng(num_refs * 100 + dim)
        refs = _bipolar(rng, (num_refs, dim))
        queries = _bipolar(rng, (16, dim))
        k = 5
        want = topk_search(queries, refs, k)
        for pack in ("auto", False):
            got = sharded_topk_search(queries, refs, k,
                                      num_shards=num_shards, pack=pack,
                                      fused=True)
            _assert_same(got, want, num_shards, pack)

    def test_fused_topk_search_packed(self):
        rng = np.random.default_rng(3)
        refs = _bipolar(rng, (50, 96))
        queries = _bipolar(rng, (9, 96))
        want = topk_search(queries, refs, 6)
        got = topk_search_packed(bitpack_bipolar(queries),
                                 bitpack_bipolar(refs), 96, 6, fused=True)
        _assert_same(got, want)

    def test_fused_fdr_routing_identical(self):
        """The whole serving search (decoy bank, shard merge, FDR) is
        unchanged by the fused flag."""
        rng = np.random.default_rng(5)
        refs = _bipolar(rng, (24, 64))
        decoys = _bipolar(rng, (24, 64))
        queries = _bipolar(rng, (7, 64))
        res = {}
        for fused in (False, True):
            db = shard_database(refs, decoys=decoys, emulate_shards=4,
                                fused=fused)
            res[fused] = search_with_fdr(db, queries, k=3, fdr=0.5)
        np.testing.assert_array_equal(res[True].indices, res[False].indices)
        np.testing.assert_array_equal(res[True].scores, res[False].scores)
        np.testing.assert_array_equal(res[True].accept, res[False].accept)
        np.testing.assert_array_equal(res[True].match, res[False].match)
