"""Unit + property tests for HD encoding, packing, and similarity."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.hd import encoding
from repro.core.hd.encoding import (
    HDEncoderConfig,
    encode_batch,
    encode_batch_reference,
    encode_bitpacked,
    make_codebooks,
    quantize_levels,
)
from repro.core.hd.packing import pack_dimensions, packed_levels, unpack_dimensions
from repro.core.hd.similarity import (
    bitpack_bipolar,
    dot_similarity,
    hamming_similarity,
    hamming_similarity_packed,
    top1_search,
    topk_search,
)


def _dataset(b=8, f=64, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.uniform(0, 1, (b, f)).astype(np.float32)
    x[rng.uniform(size=(b, f)) < 0.7] = 0.0  # sparse like spectra
    return jnp.asarray(x)


class TestCodebooks:
    def test_shapes_and_values(self):
        cfg = HDEncoderConfig(dim=256, num_features=32, num_levels=8)
        id_hvs, lv_hvs = make_codebooks(cfg)
        assert id_hvs.shape == (32, 256) and lv_hvs.shape == (8, 256)
        assert set(np.unique(id_hvs)) <= {-1, 1}
        assert set(np.unique(lv_hvs)) <= {-1, 1}

    def test_level_similarity_decays_monotonically(self):
        cfg = HDEncoderConfig(dim=2048, num_levels=16)
        _, lv = make_codebooks(cfg)
        sims = [int(jnp.dot(lv[0].astype(jnp.int32), lv[k].astype(jnp.int32)))
                for k in range(16)]
        # sim(LV_0, LV_k) decreases in k; endpoints near-orthogonal
        assert all(sims[i] >= sims[i + 1] - 1 for i in range(15))
        assert abs(sims[-1]) < 0.15 * 2048

    def test_id_orthogonality(self):
        cfg = HDEncoderConfig(dim=4096, num_features=16)
        id_hvs, _ = make_codebooks(cfg)
        g = np.asarray(dot_similarity(id_hvs, id_hvs)).astype(float)
        off = g - np.diag(np.diag(g))
        assert np.abs(off).max() < 0.1 * 4096


class TestEncoding:
    def test_blocked_matches_reference(self):
        cfg = HDEncoderConfig(dim=128, num_features=100, num_levels=8)
        id_hvs, lv_hvs = make_codebooks(cfg)
        x = _dataset(6, 100)
        a = encode_batch(x, id_hvs, lv_hvs)
        b = encode_batch_reference(x, id_hvs, lv_hvs)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_bitpacked_chunks_match_reference(self, monkeypatch):
        """Chunked bank encoding, ragged last chunk included, packs the
        reference HVs row for row."""
        monkeypatch.setattr(encoding, "_CHUNK_ROWS", 4)
        cfg = HDEncoderConfig(dim=128, num_features=100, num_levels=8)
        id_hvs, lv_hvs = make_codebooks(cfg)
        x = _dataset(11, 100, seed=3)
        got = encode_bitpacked(quantize_levels(x, 8), id_hvs, lv_hvs)
        want = bitpack_bipolar(encode_batch_reference(x, id_hvs, lv_hvs))
        assert got.dtype == jnp.uint32 and got.shape == (11, 4)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_zero_spectrum_is_all_minus_one(self):
        cfg = HDEncoderConfig(dim=64, num_features=16, num_levels=4)
        id_hvs, lv_hvs = make_codebooks(cfg)
        out = encode_batch_reference(jnp.zeros((1, 16)), id_hvs, lv_hvs)
        assert np.all(np.asarray(out) == -1)  # paper's sign(0) = -1

    def test_level_zero_reserved_for_absent(self):
        lv = quantize_levels(jnp.asarray([0.0, 1e-9, 0.01, 0.5, 1.0]), 8)
        assert lv[0] == 0 and lv[1] == 0
        assert int(lv[2]) >= 1 and int(lv[4]) == 7

    @settings(max_examples=20, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_similar_inputs_similar_hvs(self, seed):
        """Property: a small perturbation must not flip most HV bits."""
        cfg = HDEncoderConfig(dim=512, num_features=64, num_levels=16,
                              seed=seed % 97)
        id_hvs, lv_hvs = make_codebooks(cfg)
        x = _dataset(1, 64, seed=seed % 31)
        noisy = jnp.clip(x + 0.02 * (x > 0), 0, 1)  # jitter present peaks
        a = encode_batch_reference(x, id_hvs, lv_hvs)
        b = encode_batch_reference(noisy, id_hvs, lv_hvs)
        agreement = float((a == b).mean())
        assert agreement > 0.8


class TestPacking:
    @settings(max_examples=30, deadline=None)
    @given(st.integers(1, 4), st.integers(0, 1000))
    def test_pack_preserves_blockwise_sums(self, n, seed):
        rng = np.random.default_rng(seed)
        d = 24 * n
        hv = jnp.asarray(rng.choice([-1, 1], (3, d)).astype(np.int8))
        packed = pack_dimensions(hv, n)
        assert packed.shape == (3, d // n)
        expect = np.asarray(hv).reshape(3, d // n, n).sum(-1)
        np.testing.assert_array_equal(np.asarray(packed), expect)
        assert np.abs(np.asarray(packed)).max() <= n

    def test_packed_dot_estimates_unpacked_dot(self):
        rng = np.random.default_rng(0)
        d, n = 3072, 3
        a = jnp.asarray(rng.choice([-1, 1], (16, d)).astype(np.int8))
        b = jnp.asarray(rng.choice([-1, 1], (16, d)).astype(np.int8))
        exact = np.asarray(dot_similarity(a, b))
        packed = np.asarray(dot_similarity(pack_dimensions(a, n),
                                           pack_dimensions(b, n)))
        # unbiased estimator: error std ~ sqrt((n-1)*D); 4 sigma bound
        err = np.abs(packed - exact)
        assert err.mean() < 4 * np.sqrt((n - 1) * d)

    def test_unpack_roundtrip_blockwise(self):
        rng = np.random.default_rng(1)
        hv = jnp.asarray(rng.choice([-1, 1], (2, 30)).astype(np.int8))
        p = pack_dimensions(hv, 3)
        u = unpack_dimensions(p, 3, 30)
        # blockwise sums must match (the information packing preserves)
        np.testing.assert_array_equal(
            np.asarray(u).reshape(2, 10, 3).sum(-1),
            np.asarray(p),
        )

    def test_levels_count(self):
        assert packed_levels(1) == 3
        assert packed_levels(3) == 7

    def test_invalid_args(self):
        hv = jnp.ones((2, 10), jnp.int8)
        with pytest.raises(ValueError):
            pack_dimensions(hv, 3)  # 10 % 3 != 0
        with pytest.raises(ValueError):
            pack_dimensions(hv, 0)


class TestSimilarity:
    def test_hamming_dot_identity(self):
        rng = np.random.default_rng(2)
        a = jnp.asarray(rng.choice([-1, 1], (4, 128)).astype(np.int8))
        b = jnp.asarray(rng.choice([-1, 1], (5, 128)).astype(np.int8))
        dots = np.asarray(dot_similarity(a, b))
        ham = np.asarray(hamming_similarity(a, b))
        np.testing.assert_array_equal(ham, (128 + dots) // 2)

    def test_bitpacked_matches_dense(self):
        rng = np.random.default_rng(3)
        a = jnp.asarray(rng.choice([-1, 1], (6, 96)).astype(np.int8))
        b = jnp.asarray(rng.choice([-1, 1], (7, 96)).astype(np.int8))
        dense = np.asarray(hamming_similarity(a, b))
        packed = np.asarray(hamming_similarity_packed(
            bitpack_bipolar(a), bitpack_bipolar(b), 96))
        np.testing.assert_array_equal(dense, packed)

    def test_top1_finds_self(self):
        rng = np.random.default_rng(4)
        refs = jnp.asarray(rng.choice([-1, 1], (20, 256)).astype(np.int8))
        idx, score = top1_search(refs[3:4], refs)
        assert int(idx[0]) == 3 and int(score[0]) == 256

    def test_topk_ordering(self):
        rng = np.random.default_rng(5)
        refs = jnp.asarray(rng.choice([-1, 1], (30, 128)).astype(np.int8))
        q = refs[:2]
        idx, vals = topk_search(q, refs, k=5)
        v = np.asarray(vals)
        assert (np.diff(v, axis=1) <= 0).all()
        assert int(idx[0, 0]) == 0 and int(idx[1, 0]) == 1
