"""Open-modification search (OMS): banded kernel vs the masked-matrix
oracle, precursor index/candidate-range semantics, and the host-side plan.

Property tests (hypothesis; the conftest shim when the package is absent)
over ragged Q/R, per-query empty windows, windows spanning tile/shard
boundaries, duplicate-score ties, and k >= window length — all in
interpret mode (tier-1, CPU). The emulated-shard OMS serving routes are
covered in tests/test_serve.py; the real 8-device mesh in its slow tier.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.hd.similarity import bitpack_bipolar
from repro.kernels.block_utils import band_tiles
from repro.kernels.topk_hamming import (
    canonicalize_overflow_slots,
    topk_hamming_banded_pallas,
)
from repro.kernels.topk_hamming.ref import topk_hamming_banded_ref
from repro.serve.oms import (
    OMSConfig,
    OMSPlan,
    PrecursorIndex,
    build_precursor_index,
    plan_candidates,
    translate_indices,
)
from repro.spectra.preprocess import candidate_window_mask

_SENTINEL = np.iinfo(np.int32).min


def _bipolar(rng, shape):
    return jnp.asarray(rng.choice([-1, 1], size=shape).astype(np.int8))


def _assert_same(got, want, *ctx):
    gi, gv = got
    wi, wv = want
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi), err_msg=str(ctx))
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv), err_msg=str(ctx))


def _random_bands(rng, q, r, *, allow_empty=True):
    """Per-query [start, start+len) bands, a mix of empty / narrow / wide."""
    starts = rng.integers(0, r + 1, q).astype(np.int32)
    lens = rng.integers(0, r + 1, q).astype(np.int32)
    lens = np.minimum(lens, r - starts)
    if not allow_empty:
        lens = np.maximum(lens, 1)
        starts = np.minimum(starts, r - 1)
    return jnp.asarray(starts), jnp.asarray(lens)


# --------------------------------------------------------------------------
# banded kernel vs the sentinel-masked full-matrix oracle
# --------------------------------------------------------------------------

def test_band_tiles_per_block(mixed_span_bands):
    """Each Q block of the mixed batch gets its own tile base and count:
    1 tile for a window ending on a tile edge, the whole budget for the
    widest block, a base clipped at the bank's end, and the edge-padded
    tail block as narrow as its real queries."""
    starts, lens, rows, budget = mixed_span_bands
    s = np.pad(starts, (0, 5), mode="edge")
    e = np.pad(starts + lens, (0, 5), mode="edge")
    tb, tc = band_tiles(jnp.asarray(s), jnp.asarray(e), block_q=8,
                        block_r=128, total_tiles=-(-rows // 128),
                        num_tiles=budget)
    np.testing.assert_array_equal(np.asarray(tb), [1, 2, 5, 4, 2])
    np.testing.assert_array_equal(np.asarray(tc), [1, 8, 8, 3, 2])


@pytest.mark.parametrize("packed", [True, False], ids=["packed", "int8"])
def test_banded_per_block_tile_counts(mixed_span_bands, packed):
    """Q blocks that need 1 to 8 tiles of an 8-tile budget each scan only
    their own count; results stay bit-identical to the masked-full-matrix
    oracle after overflow canonicalization."""
    starts, lens, rows, budget = mixed_span_bands
    rng = np.random.default_rng(29 + packed)
    q = starts.shape[0]
    if packed:
        dim = 64
        qs = jnp.asarray(rng.integers(0, 2**32, (q, 2), dtype=np.uint32))
        rs = jnp.asarray(rng.integers(0, 2**32, (rows, 2), dtype=np.uint32))
    else:
        dim = 40
        qs, rs = _bipolar(rng, (q, dim)), _bipolar(rng, (rows, dim))
    starts, lens = jnp.asarray(starts), jnp.asarray(lens)
    got = topk_hamming_banded_pallas(qs, rs, starts, lens, dim=dim, k=5,
                                     num_tiles=budget, block_q=8,
                                     block_r=128)
    want = topk_hamming_banded_ref(qs, rs, starts, lens, dim, 5)
    _assert_same(got, want, packed)


class TestBandedVsOracleProperties:
    @settings(max_examples=10)
    @given(st.integers(1, 33), st.integers(1, 300), st.integers(1, 5),
           st.integers(1, 9))
    def test_packed_random_bands(self, q, r, w, k):
        """Random per-query bands (empty ones included, and k wider than
        many bands) over the packed XOR+popcount path."""
        k = min(k, r)
        rng = np.random.default_rng(q * 7919 + r * 131 + w * 17 + k)
        qp = jnp.asarray(rng.integers(0, 2**32, (q, w), dtype=np.uint32))
        rp = jnp.asarray(rng.integers(0, 2**32, (r, w), dtype=np.uint32))
        starts, lens = _random_bands(rng, q, r)
        got = topk_hamming_banded_pallas(qp, rp, starts, lens, dim=w * 32, k=k)
        want = topk_hamming_banded_ref(qp, rp, starts, lens, w * 32, k)
        _assert_same(got, want, q, r, w, k)

    @settings(max_examples=8)
    @given(st.integers(1, 17), st.integers(1, 150), st.integers(1, 80),
           st.integers(1, 8))
    def test_int8_dot_random_bands(self, q, r, d, k):
        """The unpacked int8-dot variant (the D % 32 != 0 fallback)."""
        k = min(k, r)
        rng = np.random.default_rng(q * 733 + r * 37 + d * 5 + k)
        qs = _bipolar(rng, (q, d))
        rs = _bipolar(rng, (r, d))
        starts, lens = _random_bands(rng, q, r)
        got = topk_hamming_banded_pallas(qs, rs, starts, lens, dim=d, k=k)
        want = topk_hamming_banded_ref(qs, rs, starts, lens, d, k)
        _assert_same(got, want, q, r, d, k)

    @settings(max_examples=8)
    @given(st.integers(2, 30), st.integers(1, 6))
    def test_duplicate_scores_tiebreak_inside_band(self, r, k):
        """Duplicated reference rows force exact score ties; the banded
        running merge must order them by ascending index like lax.top_k
        over the masked matrix."""
        rng = np.random.default_rng(r * 101 + k)
        base = _bipolar(rng, (r, 32))
        refs = jnp.concatenate([base, base, base], axis=0)  # 3r rows, tied
        queries = base[: min(r, 8)]
        q = queries.shape[0]
        k = min(k, 3 * r)
        starts, lens = _random_bands(rng, q, 3 * r, allow_empty=False)
        got = topk_hamming_banded_pallas(
            bitpack_bipolar(queries), bitpack_bipolar(refs), starts, lens,
            dim=32, k=k)
        want = topk_hamming_banded_ref(
            bitpack_bipolar(queries), bitpack_bipolar(refs), starts, lens,
            32, k)
        _assert_same(got, want, r, k)

    @settings(max_examples=8)
    @given(st.integers(1, 16), st.integers(1, 9))
    def test_band_narrower_than_k_canonical_overflow(self, q, k):
        """k >= window length: overflow slots must carry the sentinel at
        the oracle's ascending *masked* rows (bit-identity includes the
        slots past the band)."""
        r = 40
        rng = np.random.default_rng(q * 31 + k)
        qp = jnp.asarray(rng.integers(0, 2**32, (q, 2), dtype=np.uint32))
        rp = jnp.asarray(rng.integers(0, 2**32, (r, 2), dtype=np.uint32))
        starts = jnp.asarray(rng.integers(0, r, q).astype(np.int32))
        lens = jnp.asarray(rng.integers(0, k, q).astype(np.int32))
        lens = jnp.minimum(lens, r - starts)
        got = topk_hamming_banded_pallas(qp, rp, starts, lens, dim=64, k=k)
        want = topk_hamming_banded_ref(qp, rp, starts, lens, 64, k)
        _assert_same(got, want, q, k)
        gi, gv = got
        n_real = np.asarray(lens)
        for i in range(q):
            assert (np.asarray(gv)[i, n_real[i]:] == _SENTINEL).all()

    def test_band_spanning_tile_boundaries(self):
        """Bands that straddle 128-row tile (== aligned shard) boundaries,
        under the tightest tile budget that still covers them."""
        rng = np.random.default_rng(0)
        r, q, w, k = 520, 12, 3, 5
        qp = jnp.asarray(rng.integers(0, 2**32, (q, w), dtype=np.uint32))
        rp = jnp.asarray(rng.integers(0, 2**32, (r, w), dtype=np.uint32))
        # every band crosses at least one multiple of 128
        starts = jnp.asarray((rng.integers(0, 3, q) * 128 + 100).astype(np.int32))
        lens = jnp.asarray(rng.integers(60, 200, q).astype(np.int32))
        lens = jnp.minimum(lens, r - starts)
        # tightest budget honouring the caller contract: cover from the
        # block's lowest start tile to its highest end tile
        tb = int(np.asarray(starts).min()) // 128
        tight = -(-int(np.asarray(starts + lens).max()) // 128) - tb
        for nt in (tight, tight + 1, None):
            got = topk_hamming_banded_pallas(qp, rp, starts, lens, dim=w * 32,
                                             k=k, num_tiles=nt)
            want = topk_hamming_banded_ref(qp, rp, starts, lens, w * 32, k)
            _assert_same(got, want, nt)

    def test_num_valid_composes_with_bands(self):
        """num_valid (shard padding) truncates every band exactly like the
        unfused per-shard mask."""
        rng = np.random.default_rng(1)
        qp = jnp.asarray(rng.integers(0, 2**32, (6, 2), dtype=np.uint32))
        rp = jnp.asarray(rng.integers(0, 2**32, (64, 2), dtype=np.uint32))
        starts = jnp.asarray(np.arange(6, dtype=np.int32) * 9)
        lens = jnp.full((6,), 30, jnp.int32)
        for nv in (0, 10, 40, 64):
            got = topk_hamming_banded_pallas(qp, rp, starts, lens, dim=64,
                                             k=4, num_valid=nv)
            want = topk_hamming_banded_ref(qp, rp, starts, lens, 64, 4,
                                           num_valid=nv)
            _assert_same(got, want, nv)

    def test_full_band_matches_unbanded_semantics(self):
        """A [0, R) band on every query degrades to the plain fused search."""
        from repro.kernels.topk_hamming import topk_hamming_pallas
        rng = np.random.default_rng(2)
        qp = jnp.asarray(rng.integers(0, 2**32, (9, 3), dtype=np.uint32))
        rp = jnp.asarray(rng.integers(0, 2**32, (77, 3), dtype=np.uint32))
        got = topk_hamming_banded_pallas(
            qp, rp, jnp.zeros(9, jnp.int32), jnp.full(9, 77, jnp.int32),
            dim=96, k=6)
        want = topk_hamming_pallas(qp, rp, dim=96, k=6)
        _assert_same(got, want)

    def test_all_empty_bands(self):
        """Every window empty: all slots are sentinel overflow, indices the
        oracle's ascending masked rows (0..k-1)."""
        rng = np.random.default_rng(3)
        qp = jnp.asarray(rng.integers(0, 2**32, (4, 1), dtype=np.uint32))
        rp = jnp.asarray(rng.integers(0, 2**32, (20, 1), dtype=np.uint32))
        z = jnp.zeros(4, jnp.int32)
        idx, vals = topk_hamming_banded_pallas(qp, rp, z, z, dim=32, k=3)
        assert (np.asarray(vals) == _SENTINEL).all()
        np.testing.assert_array_equal(np.asarray(idx),
                                      np.broadcast_to(np.arange(3), (4, 3)))

    def test_canonicalize_overflow_multi_band(self):
        """Two disjoint bands per query: overflow slots walk the three
        masked runs ([0,s0), [e0,s1), [e1,R)) in ascending order."""
        starts = jnp.asarray([[4], [10]], jnp.int32)   # (B=2, Q=1)
        ends = jnp.asarray([[5], [11]], jnp.int32)
        idx = jnp.asarray([[4, 10, -7, -7, -7]], jnp.int32)
        vals = jnp.asarray([[3, 1, _SENTINEL, _SENTINEL, _SENTINEL]],
                           jnp.int32)
        out = canonicalize_overflow_slots(idx, vals, starts, ends, 12)
        # masked rows ascending: 0,1,2,3, 5..9, 11
        np.testing.assert_array_equal(np.asarray(out), [[4, 10, 0, 1, 2]])


# --------------------------------------------------------------------------
# precursor index + candidate ranges == candidate_window_mask
# --------------------------------------------------------------------------

class TestPrecursorIndex:
    @settings(max_examples=10)
    @given(st.integers(1, 60), st.integers(1, 40), st.integers(0, 1))
    def test_ranges_select_exactly_the_window_mask(self, r, q, open_s):
        """For every query, the sorted rows inside the [start, len) ranges
        are exactly the rows candidate_window_mask keeps — strict bounds,
        both conventions, through the permutation."""
        rng = np.random.default_rng(r * 71 + q * 3 + open_s)
        ref_prec = rng.uniform(400, 1600, r).astype(np.float32)
        query_prec = rng.uniform(350, 1800, q).astype(np.float32)
        cfg = OMSConfig(tol=25.0, open_tol=180.0, open_search=bool(open_s))
        index = build_precursor_index(ref_prec)
        starts, lens = index.candidate_ranges(query_prec, cfg)
        mask = np.asarray(candidate_window_mask(
            jnp.asarray(query_prec), jnp.asarray(ref_prec), tol=cfg.tol,
            open_search=cfg.open_search, open_tol=cfg.open_tol))
        for i in range(q):
            rows = index.perm[starts[0, i]:starts[0, i] + lens[0, i]]
            assert set(rows.tolist()) == set(np.flatnonzero(mask[i]).tolist())

    def test_two_block_layout_keeps_decoys_first(self):
        rng = np.random.default_rng(9)
        tgt = rng.uniform(400, 1600, 15).astype(np.float32)
        dec = rng.uniform(400, 1600, 15).astype(np.float32)
        index = build_precursor_index(tgt, dec)
        assert index.block_bounds == (0, 15, 30)
        # decoy rows keep original indices < 15, targets >= 15: the global
        # order the decoy-wins-ties merge convention relies on
        assert (index.perm[:15] < 15).all() and (index.perm[15:] >= 15).all()
        # ascending within each block
        assert (np.diff(index.prec_sorted[:15]) >= 0).all()
        assert (np.diff(index.prec_sorted[15:]) >= 0).all()

    def test_empty_bank(self):
        index = build_precursor_index(np.asarray([], np.float32))
        assert index.num_rows == 0
        starts, lens = index.candidate_ranges(
            np.asarray([500.0], np.float32), OMSConfig())
        assert lens.sum() == 0

    def test_translate_indices_roundtrip(self):
        rng = np.random.default_rng(11)
        prec = rng.uniform(400, 1600, 20).astype(np.float32)
        index = build_precursor_index(prec)
        rows = np.arange(20)
        np.testing.assert_array_equal(
            np.sort(translate_indices(index, rows)), rows)


class TestOMSPlan:
    def test_plan_covers_every_band(self):
        """The invariant the kernel relies on: every query's band fits in
        num_tiles tiles starting at its Q block's lowest start tile."""
        rng = np.random.default_rng(13)
        prec = np.sort(rng.uniform(400, 1600, 700)).astype(np.float32)
        index = build_precursor_index(prec)
        qp = rng.uniform(450, 1550, 37).astype(np.float32)
        plan = plan_candidates(index, qp, OMSConfig(tol=10.0, open_tol=120.0),
                               num_rows_padded=768)
        bq, br = min(128, 40), 128
        for b in range(plan.starts.shape[0]):
            s, e = plan.starts[b], plan.starts[b] + plan.lens[b]
            for i in range(0, 37, bq):
                tb = int(s[i:i + bq].min()) // br
                assert int(e[i:i + bq].max()) <= (tb + plan.num_tiles) * br
        assert 0.0 < plan.candidate_fraction < 1.0
        assert 0.0 < plan.scanned_fraction <= 1.0

    def test_sorted_queries_shrink_the_scan(self):
        """Precursor-sorting the batch (what the server does) plus the
        serving path's narrow Q blocks keeps the scanned span near the
        window width — a genuine sub-linear scan, not a full pass."""
        rng = np.random.default_rng(17)
        prec = np.sort(rng.uniform(400, 1600, 2000)).astype(np.float32)
        index = build_precursor_index(prec)
        qp = rng.uniform(450, 1550, 64).astype(np.float32)
        cfg = OMSConfig(tol=5.0, open_tol=100.0)
        unsorted = plan_candidates(index, qp, cfg, num_rows_padded=2048,
                                   block_q=8)
        srt = plan_candidates(index, np.sort(qp), cfg, num_rows_padded=2048,
                              block_q=8)
        assert srt.num_tiles <= unsorted.num_tiles
        assert srt.scanned_fraction < 1.0

    def test_scanned_fraction_counts_visited_tiles(self):
        """``scanned_fraction`` is the sum of the per-(band, block) tile
        counts the kernel visits (the device's ``band_tiles`` on the same
        windows), over blocks x bank tiles; ``budget_fraction`` and
        ``num_tiles`` stay the static grid's, so no new program compiles."""
        rng = np.random.default_rng(19)
        prec = rng.uniform(400, 1600, 6000).astype(np.float32)
        index = build_precursor_index(prec, prec)  # decoy and target bands
        qp = np.sort(rng.uniform(400, 1600, 61)).astype(np.float32)
        rows_padded, bq, br = 12032, 8, 128
        plan = plan_candidates(index, qp, OMSConfig(tol=2.5, open_tol=27.5),
                               num_rows_padded=rows_padded, block_q=bq)

        total = rows_padded // br
        pad = ((0, 0), (0, (-qp.shape[0]) % bq))
        s = np.pad(plan.starts, pad, mode="edge")
        e = np.pad(plan.starts + plan.lens, pad, mode="edge")
        lo = s.reshape(2, -1, bq).min(axis=2) // br
        hi = -(-e.reshape(2, -1, bq).max(axis=2) // br)
        span = int((hi - lo).max())
        nt = min(1 << (span - 1).bit_length(), total)
        assert plan.num_tiles == nt
        counts = np.clip(hi - np.clip(lo, 0, total - nt), 1, nt)
        for b in range(2):
            _, tc = band_tiles(jnp.asarray(s[b]), jnp.asarray(e[b]),
                               block_q=bq, block_r=br, total_tiles=total,
                               num_tiles=nt)
            np.testing.assert_array_equal(np.asarray(tc), counts[b])
        n_blocks = lo.shape[1]
        assert plan.scanned_fraction == counts.sum() / (n_blocks * total)
        assert plan.budget_fraction == min(1.0, 2 * nt / total)
        assert plan.scanned_fraction < plan.budget_fraction

    def test_has_candidate_flags_empty_windows(self):
        prec = np.asarray([500.0, 510.0], np.float32)
        index = build_precursor_index(prec)
        plan = plan_candidates(index, np.asarray([505.0, 5000.0], np.float32),
                               OMSConfig(), num_rows_padded=128)
        np.testing.assert_array_equal(plan.has_candidate, [True, False])
        assert isinstance(plan, OMSPlan)
        assert isinstance(index, PrecursorIndex)
