"""Streaming ingestion tests: append-only delta banks, exact merged
base+delta search (property-tested bit-identical to a from-scratch
rebuild, exact and OMS, across emulated shard counts, packed/int8
storage, and injected score ties), background compaction (threshold,
atomicity under injected build failures, idempotence, interleaved
queries), registry counters/validation, and the full server delta path
through FDR. The real 8-device mesh variant lives in the slow tier."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.serve import (
    BankRegistry,
    DBSearchServer,
    DeltaBank,
    OMSConfig,
    encode_queries,
    merged_oms_plan,
    merged_oms_search_encoded,
    merged_search_encoded,
    oms_search,
    search_database,
    shard_database,
)

REPO = Path(__file__).resolve().parent.parent

D = 64
K = 5


def _bip(rng, shape):
    return rng.choice([-1, 1], size=shape).astype(np.int8)


def _fixture(seed):
    """Fixed shapes (so jit signatures are shared across property
    examples), random content, ties injected across every block pair."""
    rng = np.random.default_rng(seed)
    refs0, dec0 = _bip(rng, (41, D)), _bip(rng, (23, D))
    refs1, dec1 = _bip(rng, (7, D)), _bip(rng, (5, D))
    refs1[0] = refs0[3]     # delta target == base target: exact score tie
    dec1[1] = dec0[2]       # delta decoy == base decoy
    refs1[2] = dec0[4]      # delta target == base decoy: decoy must win ties
    q = _bip(rng, (12, D))
    q[5] = refs1[0]         # a query sitting exactly on the tied rows
    return refs0, dec0, refs1, dec1, q


def _rebuilt(refs0, dec0, refs1, dec1, **kw):
    return shard_database(jnp.asarray(np.concatenate([refs0, refs1])),
                          decoys=jnp.asarray(np.concatenate([dec0, dec1])),
                          **kw)


# --------------------------------------------------------------------------
# library level: merged base+delta search == from-scratch rebuild
# --------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([1, 2, 4, 8]))
def test_merged_search_bit_identical_to_rebuild(seed, shards):
    refs0, dec0, refs1, dec1, q = _fixture(seed)
    qj = jnp.asarray(q)
    for pack in (True, False):
        base = shard_database(jnp.asarray(refs0), decoys=jnp.asarray(dec0),
                              pack=pack, emulate_shards=shards)
        delta = DeltaBank(D, oms=False)
        delta.append(refs1[:3], dec1[:2])
        delta.append(refs1[3:], dec1[2:])  # accumulation across appends
        mi, mv = merged_search_encoded(base, delta, encode_queries(base, qj),
                                       qj, K)
        oi, ov = search_database(
            _rebuilt(refs0, dec0, refs1, dec1, pack=pack,
                     emulate_shards=shards), qj, K)
        assert (np.asarray(mi) == np.asarray(oi)).all(), (seed, shards, pack)
        assert (np.asarray(mv) == np.asarray(ov)).all(), (seed, shards, pack)


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([1, 2, 4, 8]))
def test_merged_oms_bit_identical_to_rebuild(seed, shards):
    refs0, dec0, refs1, dec1, q = _fixture(seed)
    rng = np.random.default_rng(seed + 1)
    prec0 = rng.uniform(400, 1600, refs0.shape[0]).astype(np.float32)
    prec1 = rng.uniform(400, 1600, refs1.shape[0]).astype(np.float32)
    prec1[0] = prec0[3]  # tied rows share a mass: both inside any window
    qprec = np.sort(rng.uniform(420, 1650, q.shape[0]).astype(np.float32))
    cfg = OMSConfig(tol=15.0, open_tol=150.0)
    qj = jnp.asarray(q)
    for pack in (True, False):
        base = shard_database(jnp.asarray(refs0), decoys=jnp.asarray(dec0),
                              pack=pack, emulate_shards=shards,
                              precursor=prec0,
                              decoy_precursor=prec0[:dec0.shape[0]])
        delta = DeltaBank(D, oms=True)
        delta.append(refs1, dec1, precursor=prec1,
                     decoy_precursor=prec1[:dec1.shape[0]])
        mplan = merged_oms_plan(base, delta, qprec, cfg)
        rebuilt = _rebuilt(refs0, dec0, refs1, dec1, pack=pack,
                           emulate_shards=shards,
                           precursor=np.concatenate([prec0, prec1]),
                           decoy_precursor=np.concatenate(
                               [prec0[:dec0.shape[0]],
                                prec1[:dec1.shape[0]]]))
        oi, ov, oplan = oms_search(rebuilt, qj, qprec, K, cfg)
        # the merged index reproduces the rebuilt bank's candidate plan
        assert (mplan.starts == oplan.starts).all(), (seed, shards, pack)
        assert (mplan.lens == oplan.lens).all(), (seed, shards, pack)
        assert (mplan.has_candidate == oplan.has_candidate).all()
        mi, mv = merged_oms_search_encoded(
            base, delta, encode_queries(base, qj), qj, mplan, K)
        assert (np.asarray(mi) == np.asarray(oi)).all(), (seed, shards, pack)
        assert (np.asarray(mv) == np.asarray(ov)).all(), (seed, shards, pack)


def test_merged_search_degenerate_block_shapes():
    """Tiny deltas (rows < k), decoy-less deltas, and decoy-less bases
    all merge bit-identically."""
    rng = np.random.default_rng(7)
    refs0, dec0 = _bip(rng, (19, D)), _bip(rng, (11, D))
    q = jnp.asarray(_bip(rng, (6, D)))
    # delta of a single ref, no decoys (delta rows < k)
    one = _bip(rng, (1, D))
    base = shard_database(jnp.asarray(refs0), decoys=jnp.asarray(dec0),
                          emulate_shards=2)
    delta = DeltaBank(D, oms=False)
    delta.append(one)
    mi, mv = merged_search_encoded(base, delta, encode_queries(base, q), q, K)
    oracle = shard_database(jnp.asarray(np.concatenate([refs0, one])),
                            decoys=jnp.asarray(dec0), emulate_shards=2)
    oi, ov = search_database(oracle, q, K)
    assert (np.asarray(mi) == np.asarray(oi)).all()
    assert (np.asarray(mv) == np.asarray(ov)).all()
    # decoy-less base, delta carrying both refs and decoys
    base2 = shard_database(jnp.asarray(refs0), emulate_shards=2)
    delta2 = DeltaBank(D, oms=False)
    refs1, dec1 = _bip(rng, (4, D)), _bip(rng, (3, D))
    delta2.append(refs1, dec1)
    mi2, mv2 = merged_search_encoded(base2, delta2,
                                     encode_queries(base2, q), q, K)
    oracle2 = shard_database(jnp.asarray(np.concatenate([refs0, refs1])),
                             decoys=jnp.asarray(dec1), emulate_shards=2)
    oi2, ov2 = search_database(oracle2, q, K)
    assert (np.asarray(mi2) == np.asarray(oi2)).all()
    assert (np.asarray(mv2) == np.asarray(ov2)).all()


# --------------------------------------------------------------------------
# DeltaBank / BankRegistry validation + counters
# --------------------------------------------------------------------------

def test_delta_bank_validation():
    d = DeltaBank(D, oms=False)
    with pytest.raises(ValueError, match="refs shape"):
        d.append(np.zeros((3, D + 1), np.int8))
    with pytest.raises(ValueError, match="decoys shape"):
        d.append(np.zeros((3, D), np.int8), np.zeros((3, D - 1), np.int8))
    with pytest.raises(ValueError, match="at least one"):
        d.append(np.zeros((0, D), np.int8))
    with pytest.raises(ValueError, match="no precursor"):
        d.append(np.zeros((2, D), np.int8), precursor=np.ones(2))
    assert d.num_rows == 0 and d.version == 0  # failed appends land nothing

    o = DeltaBank(D, oms=True)
    with pytest.raises(ValueError, match="requires precursor"):
        o.append(np.ones((2, D), np.int8))
    with pytest.raises(ValueError, match="precursor has 3"):
        o.append(np.ones((2, D), np.int8), precursor=np.ones(3))
    with pytest.raises(ValueError, match="decoy_precursor has 1"):
        o.append(np.ones((2, D), np.int8), np.ones((2, D), np.int8),
                 precursor=np.ones(2), decoy_precursor=np.ones(1))
    assert o.append(np.ones((2, D), np.int8), precursor=np.ones(2)) == 2


def test_registry_append_counters_and_guards():
    rng = np.random.default_rng(3)
    reg = BankRegistry(emulate_shards=2)
    refs, dec = _bip(rng, (20, D)), _bip(rng, (10, D))
    reg.register("a", jnp.asarray(refs), decoys=jnp.asarray(dec))
    with pytest.raises(KeyError):
        reg.append("nope", _bip(rng, (1, D)))
    # adopted (spec-less) banks cannot accept appends
    reg.adopt("pre", shard_database(jnp.asarray(refs)))
    with pytest.raises(ValueError, match="adopted"):
        reg.append("pre", _bip(rng, (1, D)))

    assert reg.delta("a") is None and reg.delta_fraction("a") == 0.0
    assert reg.append("a", _bip(rng, (4, D)), _bip(rng, (2, D))) == 6
    assert reg.append("a", _bip(rng, (2, D))) == 8
    assert reg.appends == 2 and reg.tenants_with_delta() == ["a"]
    assert reg.delta_fraction("a") == pytest.approx(8 / 38)
    s = reg.summary()
    assert s["appends"] == 2 and s["compactions"] == 0
    assert s["delta_rows"] == 8 and s["tenants_with_delta"] == 1
    # re-registering drops the pending delta with the stale spec
    reg.register("a", jnp.asarray(refs), decoys=jnp.asarray(dec))
    assert reg.delta("a") is None and reg.tenants_with_delta() == []


def test_compaction_folds_delta_and_is_idempotent():
    rng = np.random.default_rng(11)
    reg = BankRegistry(emulate_shards=2)
    refs, dec = _bip(rng, (24, D)), _bip(rng, (12, D))
    refs1, dec1 = _bip(rng, (6, D)), _bip(rng, (3, D))
    reg.register("a", jnp.asarray(refs), decoys=jnp.asarray(dec))
    assert reg.compact("a") is False  # nothing to compact
    reg.append("a", refs1, dec1)
    q = jnp.asarray(_bip(rng, (8, D)))
    db, delta = reg.get_with_delta("a")
    before = merged_search_encoded(db, delta, encode_queries(db, q), q, K)
    assert reg.compact("a") is True
    db2, delta2 = reg.get_with_delta("a")
    assert delta2 is None and reg.compactions == 1
    assert db2.num_rows == 45 and db2.num_decoys == 15
    after = search_database(db2, q, K)
    assert (np.asarray(before[0]) == np.asarray(after[0])).all()
    assert (np.asarray(before[1]) == np.asarray(after[1])).all()
    assert reg.compact("a") is False and reg.compactions == 1  # idempotent


def test_compaction_atomic_under_build_failure(monkeypatch):
    """A failing merged build leaves the registry exactly as it was: old
    bank still served, delta still pending, counters untouched."""
    rng = np.random.default_rng(13)
    reg = BankRegistry(emulate_shards=2)
    refs, dec = _bip(rng, (16, D)), _bip(rng, (8, D))
    reg.register("a", jnp.asarray(refs), decoys=jnp.asarray(dec))
    reg.append("a", _bip(rng, (4, D)))
    old_db = reg.get("a")
    import repro.serve.db_search as db_search_mod

    def boom(*a, **kw):
        raise RuntimeError("injected build failure")

    monkeypatch.setattr(db_search_mod, "shard_database", boom)
    with pytest.raises(RuntimeError, match="injected"):
        reg.compact("a")
    monkeypatch.undo()
    assert reg.get("a") is old_db
    assert reg.delta("a") is not None and reg.delta("a").num_rows == 4
    assert reg.compactions == 0 and reg.tenants_with_delta() == ["a"]


# --------------------------------------------------------------------------
# server level: delta path through FDR, compaction between batches
# --------------------------------------------------------------------------

def _drain_results(server, queries, tenant, prec=None):
    rids = [server.submit(q, tenant=tenant,
                          precursor=None if prec is None else float(prec[i]))
            for i, q in enumerate(queries)]
    done = {r.rid: r for r in server.run_until_drained()}
    return [done[rid].result for rid in rids]


def _assert_results_equal(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert (np.asarray(g.indices) == np.asarray(w.indices)).all()
        assert (np.asarray(g.scores) == np.asarray(w.scores)).all()
        assert g.is_target == w.is_target and g.accept == w.accept
        assert g.match == w.match and g.has_candidate == w.has_candidate


def test_server_delta_path_matches_rebuilt_through_fdr():
    rng = np.random.default_rng(17)
    refs0, dec0 = _bip(rng, (30, D)), _bip(rng, (15, D))
    refs1, dec1 = _bip(rng, (6, D)), _bip(rng, (3, D))
    refs1[1] = refs0[0]  # tie across the append boundary
    queries = list(_bip(rng, (10, D)))
    queries[2] = refs1[1].copy()

    live_reg = BankRegistry(emulate_shards=2)
    live_reg.register("a", jnp.asarray(refs0), decoys=jnp.asarray(dec0))
    live = DBSearchServer(live_reg, k=4, fdr=0.5, max_batch_size=4,
                          flush_timeout_s=0.0)
    live.append("a", refs1, dec1)

    oracle_reg = BankRegistry(emulate_shards=2)
    oracle_reg.register("a", jnp.asarray(np.concatenate([refs0, refs1])),
                        decoys=jnp.asarray(np.concatenate([dec0, dec1])))
    oracle = DBSearchServer(oracle_reg, k=4, fdr=0.5, max_batch_size=4,
                            flush_timeout_s=0.0)

    _assert_results_equal(_drain_results(live, queries, "a"),
                          _drain_results(oracle, queries, "a"))
    ing = live.summary()["ingest"]
    assert ing["appends"] == 1 and ing["tenants_with_delta"] == ["a"]


def test_server_oms_delta_path_matches_rebuilt_through_fdr():
    rng = np.random.default_rng(19)
    refs0, dec0 = _bip(rng, (30, D)), _bip(rng, (15, D))
    refs1, dec1 = _bip(rng, (6, D)), _bip(rng, (3, D))
    prec0 = rng.uniform(400, 1600, 30).astype(np.float32)
    prec1 = rng.uniform(400, 1600, 6).astype(np.float32)
    queries = list(_bip(rng, (10, D)))
    qprec = rng.uniform(420, 1650, 10).astype(np.float32)  # unsorted
    cfg = OMSConfig(tol=15.0, open_tol=150.0)

    live_reg = BankRegistry(emulate_shards=2)
    live_reg.register("a", jnp.asarray(refs0), decoys=jnp.asarray(dec0),
                      precursor=prec0, decoy_precursor=prec0[:15])
    live = DBSearchServer(live_reg, k=4, fdr=0.5, max_batch_size=4,
                          flush_timeout_s=0.0, oms=cfg)
    live.append("a", refs1, dec1, precursor=prec1,
                decoy_precursor=prec1[:3])

    oracle_reg = BankRegistry(emulate_shards=2)
    oracle_reg.register(
        "a", jnp.asarray(np.concatenate([refs0, refs1])),
        decoys=jnp.asarray(np.concatenate([dec0, dec1])),
        precursor=np.concatenate([prec0, prec1]),
        decoy_precursor=np.concatenate([prec0[:15], prec1[:3]]))
    oracle = DBSearchServer(oracle_reg, k=4, fdr=0.5, max_batch_size=4,
                            flush_timeout_s=0.0, oms=cfg)

    _assert_results_equal(_drain_results(live, queries, "a", qprec),
                          _drain_results(oracle, queries, "a", qprec))


def test_server_compacts_between_batches_without_dropping_requests():
    """Queries queued before a threshold-crossing append survive the
    compaction (it runs between batches) and return the rebuilt bank's
    exact results."""
    rng = np.random.default_rng(23)
    refs0, dec0 = _bip(rng, (20, D)), _bip(rng, (10, D))
    refs1, dec1 = _bip(rng, (8, D)), _bip(rng, (4, D))
    queries = list(_bip(rng, (8, D)))

    reg = BankRegistry(emulate_shards=2)
    reg.register("a", jnp.asarray(refs0), decoys=jnp.asarray(dec0))
    srv = DBSearchServer(reg, k=4, fdr=0.5, max_batch_size=4,
                         flush_timeout_s=0.0, compact_threshold=0.25)
    # small append below the threshold: delta stays pending across steps
    srv.append("a", refs1[:1])
    srv.submit(queries[0], tenant="a")
    srv.run_until_drained()
    assert reg.tenants_with_delta() == ["a"] and reg.compactions == 0
    # queue first, then cross the threshold; the drain must compact first
    rids = [srv.submit(q, tenant="a") for q in queries]
    srv.append("a", refs1[1:], dec1)
    done = {r.rid: r for r in srv.run_until_drained()}
    assert sorted(done) == sorted(rids)  # nothing dropped
    assert reg.compactions == 1 and reg.tenants_with_delta() == []

    oracle_reg = BankRegistry(emulate_shards=2)
    oracle_reg.register("a", jnp.asarray(np.concatenate([refs0, refs1])),
                        decoys=jnp.asarray(np.concatenate([dec0, dec1])))
    oracle = DBSearchServer(oracle_reg, k=4, fdr=0.5, max_batch_size=4,
                            flush_timeout_s=0.0)
    _assert_results_equal([done[r].result for r in rids],
                          _drain_results(oracle, queries, "a"))
    ing = srv.summary()["ingest"]
    assert ing["compactions"] == 1 and ing["compact_threshold"] == 0.25


def test_server_compact_threshold_validation():
    reg = BankRegistry()
    with pytest.raises(ValueError, match="compact_threshold"):
        DBSearchServer(reg, compact_threshold=0.0)
    with pytest.raises(ValueError, match="compact_threshold"):
        DBSearchServer(reg, compact_threshold=1.5)


# --------------------------------------------------------------------------
# real multi-device shard_map path (slow tier)
# --------------------------------------------------------------------------

def _run_py(code: str, devices: int = 8, timeout: int = 520):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)


@pytest.mark.slow
def test_merged_search_bit_identical_on_8_device_mesh():
    """Base bank sharded over a real mesh, delta on one device: the merged
    search must still be bit-identical to a rebuilt mesh-sharded bank."""
    r = _run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.serve import (DeltaBank, OMSConfig, encode_queries,
                                 merged_oms_plan, merged_oms_search_encoded,
                                 merged_search_encoded, oms_search,
                                 search_database, shard_database)
        rng = np.random.default_rng(29)
        D, k = 64, 4
        refs0 = rng.choice([-1, 1], (57, D)).astype(np.int8)
        dec0 = rng.choice([-1, 1], (31, D)).astype(np.int8)
        refs1 = rng.choice([-1, 1], (6, D)).astype(np.int8)
        dec1 = rng.choice([-1, 1], (3, D)).astype(np.int8)
        refs1[0] = refs0[3]
        prec0 = rng.uniform(400, 1600, 57).astype(np.float32)
        prec1 = rng.uniform(400, 1600, 6).astype(np.float32)
        q = jnp.asarray(rng.choice([-1, 1], (12, D)).astype(np.int8))
        qprec = np.sort(rng.uniform(420, 1650, 12).astype(np.float32))
        cfg = OMSConfig(tol=15.0, open_tol=150.0)
        cat = lambda a, b: jnp.asarray(np.concatenate([a, b]))
        for model_n in (2, 4, 8):
            mesh = make_mesh((8 // model_n, model_n), ("data", "model"))
            for pack in (True, False):
                base = shard_database(jnp.asarray(refs0),
                                      decoys=jnp.asarray(dec0),
                                      mesh=mesh, pack=pack)
                delta = DeltaBank(D, oms=False)
                delta.append(refs1, dec1)
                mi, mv = merged_search_encoded(
                    base, delta, encode_queries(base, q), q, k)
                oi, ov = search_database(
                    shard_database(cat(refs0, refs1),
                                   decoys=cat(dec0, dec1),
                                   mesh=mesh, pack=pack), q, k)
                assert (np.asarray(mi) == np.asarray(oi)).all(), (model_n, pack)
                assert (np.asarray(mv) == np.asarray(ov)).all(), (model_n, pack)
                obase = shard_database(jnp.asarray(refs0),
                                       decoys=jnp.asarray(dec0),
                                       mesh=mesh, pack=pack, precursor=prec0,
                                       decoy_precursor=prec0[:31])
                odelta = DeltaBank(D, oms=True)
                odelta.append(refs1, dec1, precursor=prec1,
                              decoy_precursor=prec1[:3])
                mplan = merged_oms_plan(obase, odelta, qprec, cfg)
                mi, mv = merged_oms_search_encoded(
                    obase, odelta, encode_queries(obase, q), q, mplan, k)
                oi, ov, _ = oms_search(
                    shard_database(cat(refs0, refs1), decoys=cat(dec0, dec1),
                                   mesh=mesh, pack=pack,
                                   precursor=np.concatenate([prec0, prec1]),
                                   decoy_precursor=np.concatenate(
                                       [prec0[:31], prec1[:3]])),
                    q, qprec, k, cfg)
                assert (np.asarray(mi) == np.asarray(oi)).all(), (model_n, pack)
                assert (np.asarray(mv) == np.asarray(ov)).all(), (model_n, pack)
        print("MERGED_8DEV_OK")
    """)
    assert "MERGED_8DEV_OK" in r.stdout, r.stdout + r.stderr
