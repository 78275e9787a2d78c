"""Fused encode->pack->search kernel vs the staged oracle.

The fused kernel (``repro.kernels.encode_search``) must be bit-identical
— indices, scores, tie order, overflow slots — to running the stages
through HBM: Eq. 1 encode, bank-form encode (bit-pack / int8 cast), then
top-k. Property tests (hypothesis; the conftest shim when the package is
absent) cover ragged Q/R shapes, the D % 32 != 0 int8 fallback,
duplicate-score ties, banded/OMS windows, and the emulated-shard routed
configurations (1/2/4/8 shards) up through the serving FDR route — all
in interpret mode (tier-1, CPU).
"""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.hd.encoding import encode_levels_batch
from repro.kernels.encode_search import (
    encode_search_banded_pallas,
    encode_search_banded_ref,
    encode_search_pallas,
    encode_search_ref,
)
from repro.serve import (
    OMSConfig,
    QueryEncoder,
    encode_queries,
    oms_plan,
    oms_search_encoded,
    oms_search_levels,
    search_database_encoded,
    search_database_levels,
    shard_database,
)


def _codebooks(rng, f, d, m):
    id_hvs = jnp.asarray(rng.choice([-1, 1], size=(f, d)).astype(np.int8))
    level_hvs = jnp.asarray(rng.choice([-1, 1], size=(m, d)).astype(np.int8))
    return id_hvs, level_hvs


def _levels(rng, q, f, m):
    return jnp.asarray(rng.integers(0, m, size=(q, f)), jnp.int32)


def _assert_same(got, want, *ctx):
    gi, gv = got
    wi, wv = want
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi),
                                  err_msg=str(ctx))
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv),
                                  err_msg=str(ctx))


# --------------------------------------------------------------------------
# kernel vs staged oracle
# --------------------------------------------------------------------------

class TestFusedVsStagedOracle:
    @settings(max_examples=8)
    @given(st.integers(1, 19), st.integers(1, 140), st.sampled_from([32, 64]),
           st.integers(1, 7))
    def test_packed_random_shapes(self, q, r, d, k):
        """Ragged Q/R over a packed bank: one dispatch == three stages."""
        k = min(k, r)
        rng = np.random.default_rng(q * 7919 + r * 131 + d + k)
        f, m = 11, 5
        id_hvs, level_hvs = _codebooks(rng, f, d, m)
        levels = _levels(rng, q, f, m)
        bank = jnp.asarray(
            rng.integers(0, 2**32, (r, d // 32), dtype=np.uint32))
        got = encode_search_pallas(levels, id_hvs, level_hvs, bank, dim=d,
                                   k=k)
        want = encode_search_ref(levels, id_hvs, level_hvs, bank, k=k)
        _assert_same(got, want, q, r, d, k)

    @settings(max_examples=8)
    @given(st.integers(1, 13), st.integers(1, 90),
           st.sampled_from([17, 40, 100]), st.integers(1, 6))
    def test_int8_fallback_random_shapes(self, q, r, d, k):
        """D % 32 != 0 routes the int8-dot tile path; same contract."""
        k = min(k, r)
        rng = np.random.default_rng(q * 733 + r * 37 + d * 5 + k)
        f, m = 9, 4
        id_hvs, level_hvs = _codebooks(rng, f, d, m)
        levels = _levels(rng, q, f, m)
        bank = jnp.asarray(rng.choice([-1, 1], size=(r, d)).astype(np.int8))
        got = encode_search_pallas(levels, id_hvs, level_hvs, bank, dim=d,
                                   k=k)
        want = encode_search_ref(levels, id_hvs, level_hvs, bank, k=k)
        _assert_same(got, want, q, r, d, k)

    @settings(max_examples=6)
    @given(st.integers(2, 20), st.integers(1, 5))
    def test_duplicate_scores_tiebreak(self, r, k):
        """A bank of each query's own encoded HV repeated 3x ties every
        repeat exactly; the fused path must keep lax.top_k's ascending-
        index tie order through the in-kernel encode."""
        k = min(k, 3 * r)
        rng = np.random.default_rng(r * 101 + k)
        f, d, m = 8, 32, 4
        id_hvs, level_hvs = _codebooks(rng, f, d, m)
        levels = _levels(rng, min(r, 6), f, m)
        hv = encode_levels_batch(levels, id_hvs, level_hvs)
        base = jnp.concatenate([hv] * max(1, -(-r // hv.shape[0])))[:r]
        bank_hv = jnp.concatenate([base, base, base], axis=0)
        from repro.core.hd.similarity import bitpack_bipolar
        bank = bitpack_bipolar(bank_hv)
        got = encode_search_pallas(levels, id_hvs, level_hvs, bank, dim=d,
                                   k=k)
        want = encode_search_ref(levels, id_hvs, level_hvs, bank, k=k)
        _assert_same(got, want, r, k)

    @pytest.mark.parametrize("num_valid", [0, 1, 5, 9, 12])
    def test_num_valid_masks_like_shard_padding(self, num_valid):
        """Rows >= num_valid are sentinel-masked with ascending overflow
        fillers — the shard-padding contract of db_search."""
        rng = np.random.default_rng(3)
        f, d, m, r, k = 10, 32, 4, 12, 6
        id_hvs, level_hvs = _codebooks(rng, f, d, m)
        levels = _levels(rng, 5, f, m)
        bank = jnp.asarray(
            rng.integers(0, 2**32, (r, 1), dtype=np.uint32))
        got = encode_search_pallas(levels, id_hvs, level_hvs, bank, dim=d,
                                   k=k, num_valid=num_valid)
        want = encode_search_ref(levels, id_hvs, level_hvs, bank, k=k,
                                 num_valid=num_valid)
        _assert_same(got, want, num_valid)

    @settings(max_examples=8)
    @given(st.integers(1, 12), st.integers(8, 90), st.sampled_from([32, 55]),
           st.integers(1, 5))
    def test_banded_windows(self, q, r, d, k):
        """Banded (OMS-window) variant vs the masked-full-matrix oracle,
        including empty and overflowing (len < k) windows."""
        k = min(k, r)
        rng = np.random.default_rng(q * 311 + r * 13 + d + k)
        f, m = 8, 4
        id_hvs, level_hvs = _codebooks(rng, f, d, m)
        levels = _levels(rng, q, f, m)
        if d % 32 == 0:
            bank = jnp.asarray(
                rng.integers(0, 2**32, (r, d // 32), dtype=np.uint32))
        else:
            bank = jnp.asarray(
                rng.choice([-1, 1], size=(r, d)).astype(np.int8))
        starts = rng.integers(0, r, size=q).astype(np.int32)
        lens = rng.integers(0, r, size=q).astype(np.int32)
        got = encode_search_banded_pallas(
            levels, id_hvs, level_hvs, bank, jnp.asarray(starts),
            jnp.asarray(lens), dim=d, k=k)
        want = encode_search_banded_ref(levels, id_hvs, level_hvs, bank,
                                        starts, lens, k=k)
        _assert_same(got, want, q, r, d, k)


@pytest.mark.parametrize("packed", [True, False], ids=["packed", "int8"])
def test_banded_per_block_tile_counts(mixed_span_bands, packed):
    """The fused banded kernel on Q blocks that need 1 to 8 tiles of an
    8-tile budget (a narrow block, one as wide as the budget, a tile base
    clipped at the bank's end, an empty window, an edge-padded tail):
    each scans only its own count, bit-identical to the staged oracle."""
    starts, lens, rows, budget = mixed_span_bands
    rng = np.random.default_rng(31 + packed)
    f, m = 8, 4
    d = 64 if packed else 55
    id_hvs, level_hvs = _codebooks(rng, f, d, m)
    levels = _levels(rng, starts.shape[0], f, m)
    if packed:
        bank = jnp.asarray(
            rng.integers(0, 2**32, (rows, d // 32), dtype=np.uint32))
    else:
        bank = jnp.asarray(rng.choice([-1, 1], size=(rows, d)).astype(np.int8))
    got = encode_search_banded_pallas(
        levels, id_hvs, level_hvs, bank, jnp.asarray(starts),
        jnp.asarray(lens), dim=d, k=5, num_tiles=budget, block_q=8,
        block_r=128)
    want = encode_search_banded_ref(levels, id_hvs, level_hvs, bank,
                                    starts, lens, k=5)
    _assert_same(got, want, packed)


# --------------------------------------------------------------------------
# routed configurations: fused e2e == staged e2e through the serve layer
# --------------------------------------------------------------------------

def _bank_inputs(seed, *, d, n_refs=30, n_decoys=30):
    rng = np.random.default_rng(seed)
    refs = jnp.asarray(rng.choice([-1, 1], size=(n_refs, d)).astype(np.int8))
    decoys = jnp.asarray(
        rng.choice([-1, 1], size=(n_decoys, d)).astype(np.int8))
    return rng, refs, decoys


class TestRoutedConfigurations:
    @pytest.mark.parametrize("shards", [1, 2, 4, 8])
    @pytest.mark.parametrize("d", [64, 48])  # packed / int8 banks
    def test_emulated_shards_exact(self, shards, d):
        rng, refs, decoys = _bank_inputs(shards * 100 + d, d=d)
        enc = QueryEncoder.from_config(dim=d, num_features=16, num_levels=6,
                                       seed=7)
        levels = _levels(rng, 9, 16, 6)
        db = shard_database(refs, decoys=decoys,
                            emulate_shards=shards if shards > 1 else None)
        fused = search_database_levels(db, enc, levels, 3, fused_e2e=True)
        staged = search_database_levels(db, enc, levels, 3)
        _assert_same(fused, staged, shards, d)
        # and the staged-levels route equals the pre-encoded-HV route
        hv = encode_levels_batch(levels, enc.id_hvs, enc.level_hvs)
        oracle = search_database_encoded(db, encode_queries(db, hv), 3)
        _assert_same(staged, oracle, shards, d)

    @pytest.mark.parametrize("shards", [1, 2, 4])
    def test_emulated_shards_oms(self, shards):
        d = 64
        rng, refs, decoys = _bank_inputs(shards * 77, d=d)
        enc = QueryEncoder.from_config(dim=d, num_features=16, num_levels=6,
                                       seed=7)
        levels = _levels(rng, 8, 16, 6)
        prec = np.sort(rng.uniform(100, 900, refs.shape[0])).astype(
            np.float32)
        qprec = np.sort(rng.uniform(100, 900, 8)).astype(np.float32)
        db = shard_database(refs, decoys=decoys, precursor=prec,
                            emulate_shards=shards if shards > 1 else None)
        plan = oms_plan(db, qprec, OMSConfig(tol=40, open_tol=250))
        fused = oms_search_levels(db, enc, levels, plan, 3, fused_e2e=True)
        staged = oms_search_levels(db, enc, levels, plan, 3)
        _assert_same(fused, staged, shards)
        hv = encode_levels_batch(levels, enc.id_hvs, enc.level_hvs)
        oracle = oms_search_encoded(db, encode_queries(db, hv), plan, 3)
        _assert_same(staged, oracle, shards)

    def test_encoder_bank_dim_mismatch_raises(self):
        rng, refs, _ = _bank_inputs(5, d=64)
        enc = QueryEncoder.from_config(dim=32, num_features=8, num_levels=4)
        db = shard_database(refs)
        with pytest.raises(ValueError, match="encoder dim"):
            search_database_levels(db, enc, _levels(rng, 2, 8, 4), 2)
