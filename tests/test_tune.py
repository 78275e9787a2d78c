"""Autotuner tests: microbench ceilings, tuning-table lifecycle, trace-time
block resolution, per-kernel validation, and the tuned == default
bit-identity property across serving configurations."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hd.similarity import bitpack_bipolar
from repro.kernels.block_utils import ALIGN, DEFAULTS, resolve_blocks
from repro.tune import table as tune_table
from repro.tune.table import (
    TuningTable,
    device_kind,
    load_table,
    lookup_blocks,
    set_active_table,
    shape_bucket,
)

RNG = np.random.default_rng(7)


@pytest.fixture(autouse=True)
def _clean_table_state(monkeypatch):
    """Every test starts and ends with no active table and a cleared
    one-time-log memory."""
    monkeypatch.delenv(tune_table.ENV_VAR, raising=False)
    tune_table.reset()
    yield
    tune_table.reset()


def bip(shape):
    return RNG.choice([-1, 1], size=shape).astype(np.int8)


# --------------------------------------------------------------------------
# microbench ceilings
# --------------------------------------------------------------------------

def test_measured_ceilings_positive_on_cpu():
    from repro.tune.microbench import measure_mem_bandwidth, measure_peak_flops
    flops = measure_peak_flops(sizes=(128, 256), iters=2)
    bw = measure_mem_bandwidth(sizes_mb=(1, 4), iters=2)
    assert flops["peak_flops"] > 0
    assert all(v > 0 for v in flops["by_size"].values())
    assert bw["hbm_bw"] > 0
    # the ceiling is the max of the sweep, by construction
    assert flops["peak_flops"] == max(flops["by_size"].values())
    assert bw["hbm_bw"] == max(bw["by_size_mb"].values())


# --------------------------------------------------------------------------
# table lifecycle
# --------------------------------------------------------------------------

def _mk_table(kind=None, **ceilings):
    return TuningTable(device_kind=kind or device_kind(),
                       ceilings=ceilings, meta={"quick": True})


def test_shape_bucket_pow2():
    assert shape_bucket((100, 8000, 32)) == "128x8192x32"
    assert shape_bucket((1,)) == "1"
    assert shape_bucket((129,)) == "256"


def test_table_roundtrip(tmp_path):
    t = _mk_table(peak_flops=1e11, hbm_bw=2e10)
    t.set_entry("topk_hamming", (100, 8000, 32),
                {"block_q": 32, "block_r": 256, "word_chunk": 128},
                us=10.0, default_us=20.0)
    path = t.save(tmp_path / "table.json")
    loaded = load_table(path)
    assert loaded is not None
    assert loaded.device_kind == t.device_kind
    assert loaded.ceilings["peak_flops"] == 1e11
    assert loaded.lookup("topk_hamming", (128, 8192, 32)) == {
        "block_q": 32, "block_r": 256, "word_chunk": 128}
    # a different bucket misses
    assert loaded.lookup("topk_hamming", (128, 1024, 32)) is None


def test_corrupt_table_falls_back(tmp_path, caplog):
    p = tmp_path / "bad.json"
    p.write_text("{not json")
    with caplog.at_level("WARNING", logger="repro.tune"):
        assert load_table(p) is None
        assert load_table(p) is None  # second load: no second log line
    assert sum("unreadable" in r.message for r in caplog.records) == 1


def test_partial_table_falls_back(tmp_path):
    p = tmp_path / "partial.json"
    p.write_text(json.dumps({"schema": 99, "device_kind": "cpu"}))
    assert load_table(p) is None


def test_misaligned_entry_dropped_at_load(tmp_path, caplog):
    t = _mk_table()
    t.set_entry("topk_hamming", (8, 128, 4),
                {"block_q": 7, "block_r": 128, "word_chunk": 128})
    t.set_entry("topk_hamming", (8, 256, 4),
                {"block_q": 8, "block_r": 128, "word_chunk": 128})
    # a lane slice Mosaic cannot prove 128-aligned
    t.set_entry("topk_hamming", (8, 512, 4),
                {"block_q": 8, "block_r": 128, "word_chunk": 32})
    path = t.save(tmp_path / "table.json")
    with caplog.at_level("WARNING", logger="repro.tune"):
        loaded = load_table(path)
    assert loaded.lookup("topk_hamming", (8, 128, 4)) is None  # dropped
    assert loaded.lookup("topk_hamming", (8, 512, 4)) is None  # dropped
    assert loaded.lookup("topk_hamming", (8, 256, 4)) is not None  # kept
    assert any("misaligned" in r.message for r in caplog.records)


def test_unknown_op_dropped_at_load(tmp_path):
    t = _mk_table()
    t.set_entry("not_a_kernel", (8,), {"block_q": 8})
    loaded = load_table(t.save(tmp_path / "table.json"))
    assert loaded.ops == {}


def test_device_kind_mismatch_ignored(tmp_path, caplog):
    t = _mk_table(kind="TPU v99")
    t.set_entry("topk_hamming", (8, 128, 4),
                {"block_q": 8, "block_r": 128, "word_chunk": 256})
    set_active_table(t.save(tmp_path / "table.json"))
    with caplog.at_level("WARNING", logger="repro.tune"):
        assert lookup_blocks("topk_hamming", (8, 128, 4)) is None
        assert lookup_blocks("topk_hamming", (8, 128, 4)) is None
    kind_logs = [r for r in caplog.records if "device kind" in r.message]
    assert len(kind_logs) == 1  # one-time log


def test_env_var_activation(tmp_path, monkeypatch):
    t = _mk_table()
    t.set_entry("topk_hamming", (8, 128, 4),
                {"block_q": 16, "block_r": 128, "word_chunk": 256})
    path = t.save(tmp_path / "table.json")
    assert lookup_blocks("topk_hamming", (8, 128, 4)) is None
    monkeypatch.setenv(tune_table.ENV_VAR, str(path))
    # env change is picked up without an explicit reset()
    assert lookup_blocks("topk_hamming", (8, 128, 4)) == {
        "block_q": 16, "block_r": 128, "word_chunk": 256}
    monkeypatch.delenv(tune_table.ENV_VAR)
    assert lookup_blocks("topk_hamming", (8, 128, 4)) is None


def test_resolve_blocks_precedence():
    t = _mk_table()
    t.set_entry("topk_hamming", (8, 128, 4),
                {"block_q": 16, "block_r": 256, "word_chunk": 256})
    set_active_table(t)
    # table beats defaults
    assert resolve_blocks("topk_hamming", (8, 128, 4),
                          {"block_q": None, "block_r": None,
                           "word_chunk": None}) == {
        "block_q": 16, "block_r": 256, "word_chunk": 256}
    # explicit beats table
    cfg = resolve_blocks("topk_hamming", (8, 128, 4),
                         {"block_q": 32, "block_r": None, "word_chunk": None})
    assert cfg["block_q"] == 32 and cfg["block_r"] == 256
    # no table entry for this bucket -> defaults
    assert resolve_blocks("topk_hamming", (64, 1024, 4),
                          {"block_q": None, "block_r": None,
                           "word_chunk": None}) == DEFAULTS["topk_hamming"]


def test_defaults_are_aligned():
    for op, cfg in DEFAULTS.items():
        for name, value in cfg.items():
            assert value % ALIGN[op][name] == 0, (op, name)


# --------------------------------------------------------------------------
# per-kernel explicit-block validation (the satellite-1 regression tests)
# --------------------------------------------------------------------------

def _topk_operands(q_n=8, r_n=128, dim=64):
    q = bitpack_bipolar(jnp.asarray(bip((q_n, dim))))
    r = bitpack_bipolar(jnp.asarray(bip((r_n, dim))))
    return q, r


def test_topk_hamming_rejects_misaligned_blocks():
    from repro.kernels.topk_hamming import topk_hamming_pallas
    q, r = _topk_operands()
    with pytest.raises(ValueError, match="block_q=7 must be a positive"):
        topk_hamming_pallas(q, r, dim=64, k=4, block_q=7)
    with pytest.raises(ValueError, match="block_r=100"):
        topk_hamming_pallas(q, r, dim=64, k=4, block_r=100)
    with pytest.raises(ValueError, match="word_chunk=-8"):
        topk_hamming_pallas(q, r, dim=64, k=4, word_chunk=-8)


def test_topk_hamming_banded_rejects_misaligned_blocks():
    from repro.kernels.topk_hamming import topk_hamming_banded_pallas
    q, r = _topk_operands()
    starts = jnp.zeros(8, jnp.int32)
    lens = jnp.full(8, 64, jnp.int32)
    with pytest.raises(ValueError, match="topk_hamming_banded: block_q=12"):
        topk_hamming_banded_pallas(q, r, starts, lens, dim=64, k=4,
                                   block_q=12)


def test_encode_search_rejects_misaligned_blocks():
    from repro.kernels.encode_search import (
        encode_search_banded_pallas,
        encode_search_pallas,
    )
    lv = jnp.asarray(RNG.integers(0, 4, size=(8, 16)).astype(np.int32))
    id_hvs = jnp.asarray(bip((16, 64)))
    level_hvs = jnp.asarray(bip((4, 64)))
    bank = bitpack_bipolar(jnp.asarray(bip((128, 64))))
    with pytest.raises(ValueError, match="block_f=5"):
        encode_search_pallas(lv, id_hvs, level_hvs, bank, dim=64, k=4,
                             block_f=5)
    starts = jnp.zeros(8, jnp.int32)
    lens = jnp.full(8, 64, jnp.int32)
    with pytest.raises(ValueError, match="word_chunk=3"):
        encode_search_banded_pallas(lv, id_hvs, level_hvs, bank, starts,
                                    lens, dim=64, k=4, word_chunk=3)


def test_hd_encode_rejects_misaligned_blocks():
    from repro.kernels.hd_encode import hd_encode_pallas
    lv = jnp.asarray(RNG.integers(0, 4, size=(8, 16)).astype(np.int32))
    id_hvs = jnp.asarray(bip((16, 128)))
    level_hvs = jnp.asarray(bip((4, 128)))
    with pytest.raises(ValueError, match="block_d=100"):
        hd_encode_pallas(lv, id_hvs, level_hvs, block_d=100)


def test_imc_mvm_rejects_misaligned_blocks():
    from repro.kernels.imc_mvm import imc_mvm_pallas
    q = jnp.asarray(RNG.standard_normal((8, 128)).astype(np.float32))
    w = jnp.asarray(RNG.standard_normal((16, 128)).astype(np.float32))
    with pytest.raises(ValueError, match="tile_cols=64"):
        imc_mvm_pallas(q, w, full_scale=128.0, tile_cols=64)


# --------------------------------------------------------------------------
# tuned == default bit-identity (the satellite-4 property suite)
# --------------------------------------------------------------------------

# a deliberately non-default (but aligned) tuned config per op
_TUNED = {
    "topk_hamming": {"block_q": 16, "block_r": 256, "word_chunk": 256},
    "topk_hamming_banded": {"block_q": 16, "block_r": 128, "word_chunk": 256},
    "encode_search": {"block_q": 16, "block_r": 256, "block_f": 256,
                      "word_chunk": 256},
}


def _install(op, shape):
    t = _mk_table()
    t.set_entry(op, shape, _TUNED[op])
    set_active_table(t)


@pytest.mark.parametrize("packed", [True, False])
@pytest.mark.parametrize("q_n,r_n,packed_dim", [
    pytest.param(5, 100, 64, id="5-100"),
    pytest.param(8, 300, 64, id="8-300"),
    pytest.param(13, 257, 64, id="13-257"),
    # 260 words: the default and tuned word chunks (128, 256) walk
    # different chunk counts and word paddings
    pytest.param(6, 130, 8320, id="6-130-W260"),
])
def test_topk_tuned_bit_identical(packed, q_n, r_n, packed_dim):
    from repro.kernels.topk_hamming import topk_hamming_pallas
    dim = packed_dim if packed else packed_dim + 32
    qb = jnp.asarray(bip((q_n, dim)))
    rb = jnp.asarray(bip((r_n, dim)))
    q = bitpack_bipolar(qb) if packed else qb
    r = bitpack_bipolar(rb) if packed else rb
    idx0, val0 = topk_hamming_pallas(q, r, dim=dim, k=4,
                                     **DEFAULTS["topk_hamming"])
    _install("topk_hamming", (q_n, r_n, q.shape[1]))
    assert resolve_blocks("topk_hamming", (q_n, r_n, q.shape[1]),
                          {"block_q": None, "block_r": None,
                           "word_chunk": None}) == _TUNED["topk_hamming"]
    idx1, val1 = topk_hamming_pallas(q, r, dim=dim, k=4)
    np.testing.assert_array_equal(np.asarray(idx0), np.asarray(idx1))
    np.testing.assert_array_equal(np.asarray(val0), np.asarray(val1))


@pytest.mark.parametrize("packed", [True, False])
def test_topk_banded_tuned_bit_identical(packed):
    from repro.kernels.topk_hamming import topk_hamming_banded_pallas
    q_n, r_n, dim = 9, 300, 96 if not packed else 64
    qb = jnp.asarray(bip((q_n, dim)))
    rb = jnp.asarray(bip((r_n, dim)))
    q = bitpack_bipolar(qb) if packed else qb
    r = bitpack_bipolar(rb) if packed else rb
    starts = jnp.asarray(RNG.integers(0, 200, size=q_n).astype(np.int32))
    lens = jnp.full(q_n, 80, jnp.int32)
    kw = dict(dim=dim, k=4, num_tiles=2)
    idx0, val0 = topk_hamming_banded_pallas(
        q, r, starts, lens, **kw, **DEFAULTS["topk_hamming_banded"])
    _install("topk_hamming_banded", (q_n, r_n, q.shape[1]))
    idx1, val1 = topk_hamming_banded_pallas(q, r, starts, lens, **kw)
    np.testing.assert_array_equal(np.asarray(idx0), np.asarray(idx1))
    np.testing.assert_array_equal(np.asarray(val0), np.asarray(val1))


@pytest.mark.parametrize("q_n,r_n", [(5, 100), (11, 260)])
def test_encode_search_tuned_bit_identical(q_n, r_n):
    from repro.kernels.encode_search import encode_search_pallas
    feats, dim, levels_n = 24, 64, 8
    lv = jnp.asarray(
        RNG.integers(0, levels_n, size=(q_n, feats)).astype(np.int32))
    id_hvs = jnp.asarray(bip((feats, dim)))
    level_hvs = jnp.asarray(bip((levels_n, dim)))
    bank = bitpack_bipolar(jnp.asarray(bip((r_n, dim))))
    idx0, val0 = encode_search_pallas(lv, id_hvs, level_hvs, bank, dim=dim,
                                      k=4, **DEFAULTS["encode_search"])
    _install("encode_search", (q_n, r_n, feats))
    idx1, val1 = encode_search_pallas(lv, id_hvs, level_hvs, bank, dim=dim,
                                      k=4)
    np.testing.assert_array_equal(np.asarray(idx0), np.asarray(idx1))
    np.testing.assert_array_equal(np.asarray(val0), np.asarray(val1))


@pytest.mark.parametrize("shards", [1, 2, 4, 8])
def test_sharded_search_tuned_bit_identical(shards):
    """The serving path (fused emulated shards) returns bit-identical
    results whether blocks come from the table or the defaults."""
    from repro.serve.db_search import search_database, shard_database
    q_n, r_n, dim = 6, 290, 64
    refs = jnp.asarray(bip((r_n, dim)))
    queries = jnp.asarray(bip((q_n, dim)))
    db = shard_database(refs, emulate_shards=shards, fused=True)
    idx0, val0 = search_database(db, queries, 5)
    t = _mk_table()
    t.set_entry("topk_hamming", (q_n, db.shard_rows, dim // 32),
                _TUNED["topk_hamming"])
    set_active_table(t)
    idx1, val1 = search_database(db, queries, 5)
    np.testing.assert_array_equal(np.asarray(idx0), np.asarray(idx1))
    np.testing.assert_array_equal(np.asarray(val0), np.asarray(val1))


def test_shard_database_block_plumbing():
    """Explicit per-bank blocks reach the kernel (and are validated)."""
    from repro.serve.db_search import search_database, shard_database
    refs = jnp.asarray(bip((200, 64)))
    queries = jnp.asarray(bip((4, 64)))
    db0 = shard_database(refs, fused=True)
    db1 = shard_database(refs, fused=True, block_q=16, block_r=256,
                         word_chunk=256)
    assert (db1.block_q, db1.block_r, db1.word_chunk) == (16, 256, 256)
    idx0, val0 = search_database(db0, queries, 3)
    idx1, val1 = search_database(db1, queries, 3)
    np.testing.assert_array_equal(np.asarray(idx0), np.asarray(idx1))
    np.testing.assert_array_equal(np.asarray(val0), np.asarray(val1))
    with pytest.raises(ValueError, match="block_r=100"):
        shard_database(refs, fused=True, block_r=100)


# --------------------------------------------------------------------------
# sweep + CLI
# --------------------------------------------------------------------------

def test_sweep_op_winner_never_slower():
    from repro.tune.sweep import sweep_op
    res = sweep_op("imc_mvm", quick=True, iters=2)
    assert res["us"] <= res["default_us"]
    assert res["blocks"].keys() == DEFAULTS["imc_mvm"].keys()


def test_tune_cli_produces_usable_table(tmp_path, capsys):
    from repro.launch.tune import main
    out = tmp_path / "table.json"
    table = main(["--out", str(out), "--quick", "--iters", "1",
                  "--ops", "imc_mvm", "--skip-ceilings"])
    assert out.exists()
    printed = capsys.readouterr().out
    assert "imc_mvm" in printed and "device_kind" in printed
    loaded = load_table(out)
    assert loaded is not None and loaded.device_kind == device_kind()
    assert "imc_mvm" in loaded.ops
    from repro.tune.sweep import tuned_vs_default_ratio
    assert tuned_vs_default_ratio(table) >= 0.95


def test_build_tuning_table_records_ceilings(tmp_path):
    from repro.tune.sweep import build_tuning_table
    table = build_tuning_table(tmp_path / "t.json", quick=True,
                               ops=("imc_mvm",), iters=1)
    assert table.ceilings["peak_flops"] > 0
    assert table.ceilings["hbm_bw"] > 0
    # the measured ceilings feed the roofline profile once active
    set_active_table(table)
    from repro.launch.roofline import active_profile
    prof = active_profile()
    assert prof.source == "measured"
    assert prof.peak_flops == table.ceilings["peak_flops"]
    assert prof.hbm_bw == table.ceilings["hbm_bw"]
