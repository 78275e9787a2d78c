"""Serving-cache layer tests: QueryHVCache LRU/byte-budget semantics,
BankRegistry lazy build + pinning + LRU eviction, shape-bucketed
dispatch, tenant-aware queue fairness, and cached-vs-cold bit-identity
of the multi-tenant server against the unsharded oracle (tier-1 via
emulated shards; the real 8-device path lives in the slow tier)."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hd.similarity import topk_search
from repro.launch.mesh import make_mesh
from repro.serve import (
    BankRegistry,
    DBSearchServer,
    MicroBatchQueue,
    QueryHVCache,
    bucket_for,
    make_buckets,
    search_database,
    shard_database,
)

REPO = Path(__file__).resolve().parent.parent


def _bipolar(rng, shape):
    return jnp.asarray(rng.choice([-1, 1], size=shape).astype(np.int8))


# --------------------------------------------------------------------------
# QueryHVCache
# --------------------------------------------------------------------------

def _row(i, n=16):
    return np.full(n, i, dtype=np.int8)


def test_query_cache_lru_eviction_order():
    # each int8 row is 16 bytes; budget fits exactly two entries
    c = QueryHVCache(capacity_bytes=32)
    ka = c.content_key(_row(1));  c.insert(ka, _row(1))
    kb = c.content_key(_row(2));  c.insert(kb, _row(2))
    assert ka in c and kb in c and c.current_bytes == 32
    # touch A so B becomes the LRU entry, then insert C: B must go
    assert c.lookup(ka) is not None
    kc = c.content_key(_row(3));  c.insert(kc, _row(3))
    assert ka in c and kc in c and kb not in c
    assert c.evictions == 1 and len(c) == 2


def test_query_cache_byte_budget_enforced():
    c = QueryHVCache(capacity_bytes=100)
    for i in range(20):
        c.insert(c.content_key(_row(i)), _row(i))  # 16 bytes each
        assert c.current_bytes <= 100
    assert len(c) == 6 and c.current_bytes == 96  # floor(100 / 16)
    assert c.evictions == 14


def test_query_cache_oversized_value_rejected():
    c = QueryHVCache(capacity_bytes=8)
    key = c.content_key(_row(1))
    assert not c.insert(key, _row(1))   # 16 bytes > 8-byte budget
    assert key not in c and len(c) == 0 and c.current_bytes == 0


def test_query_cache_counters_and_get_or_encode():
    c = QueryHVCache(capacity_bytes=1 << 10)
    raw = _row(7)
    calls = []

    def encode(x):
        calls.append(1)
        return x.astype(np.int32) * 2

    v1, hit1 = c.get_or_encode(raw, encode)
    v2, hit2 = c.get_or_encode(raw, encode)
    assert not hit1 and hit2 and len(calls) == 1
    np.testing.assert_array_equal(v1, v2)
    assert c.hits == 1 and c.misses == 1 and c.hit_rate == 0.5
    # the same bytes under a different encoding variant is a distinct entry
    _, hit3 = c.get_or_encode(raw, encode, variant="other")
    assert not hit3 and len(calls) == 2


def test_query_cache_content_key_distinguishes_dtype_and_shape():
    a = np.zeros(8, np.int8)
    assert QueryHVCache.content_key(a) != QueryHVCache.content_key(
        a.astype(np.int16)[:4])
    assert QueryHVCache.content_key(a) != QueryHVCache.content_key(
        a.reshape(2, 4))


# --------------------------------------------------------------------------
# BankRegistry
# --------------------------------------------------------------------------

def test_bank_registry_lazy_build_and_rebuild():
    rng = np.random.default_rng(41)
    reg = BankRegistry(max_banks=2)
    for t in range(3):
        reg.register(f"t{t}", _bipolar(rng, (10 + t, 32)))
    assert reg.builds == 0 and not any(reg.is_built(f"t{t}") for t in range(3))
    assert reg.dim("t0") == 32  # available without building

    db0 = reg.get("t0")
    assert reg.builds == 1 and reg.is_built("t0")
    assert db0.num_rows == 10
    assert reg.get("t0") is db0 and reg.hits == 1  # cached handle

    reg.get("t1")
    reg.get("t2")                       # 3 built > max_banks=2: t0 evicted
    assert not reg.is_built("t0") and reg.evictions == 1
    db0b = reg.get("t0")                # transparently rebuilt from the spec
    assert db0b.num_rows == 10 and reg.builds == 4


def test_bank_registry_pinning_exempts_from_eviction():
    rng = np.random.default_rng(43)
    reg = BankRegistry(max_banks=1)
    reg.register("hot", _bipolar(rng, (8, 32)), pin=True)
    reg.register("cold", _bipolar(rng, (8, 32)))
    reg.get("hot")
    reg.get("cold")
    # 'hot' is older but pinned: 'cold' must be the eviction victim
    assert reg.is_built("hot") and not reg.is_built("cold")
    reg.unpin("hot")
    reg.get("cold")
    assert not reg.is_built("hot") and reg.is_built("cold")


def test_bank_registry_decoys_and_shard_options():
    rng = np.random.default_rng(47)
    reg = BankRegistry(emulate_shards=4)
    reg.register("t", _bipolar(rng, (9, 32)), decoys=_bipolar(rng, (5, 32)))
    db = reg.get("t")
    assert db.num_rows == 14 and db.num_decoys == 5
    assert db.num_shards == 4 and db.shard_rows == 4


def test_bank_registry_unknown_tenant_raises():
    reg = BankRegistry()
    with pytest.raises(KeyError):
        reg.get("nope")
    with pytest.raises(KeyError):
        reg.dim("nope")


# --------------------------------------------------------------------------
# shape buckets
# --------------------------------------------------------------------------

def test_make_buckets_geometric_ladder():
    assert make_buckets(32, 4) == (4, 8, 16, 32)
    assert make_buckets(32, 1) == (32,)
    assert make_buckets(3, 8) == (1, 3)  # ladder stops at 1
    assert make_buckets(1, 4) == (1,)


def test_bucket_for_smallest_cover():
    buckets = (4, 8, 16)
    assert bucket_for(1, buckets) == 4
    assert bucket_for(4, buckets) == 4
    assert bucket_for(5, buckets) == 8
    assert bucket_for(16, buckets) == 16
    with pytest.raises(ValueError, match="exceeds"):
        bucket_for(17, buckets)


# --------------------------------------------------------------------------
# tenant-aware queue
# --------------------------------------------------------------------------

def test_queue_batches_are_tenant_homogeneous():
    q = MicroBatchQueue(max_batch_size=8, flush_timeout_s=0.0)
    q.submit("a0", tenant="a")
    q.submit("b0", tenant="b")
    q.submit("a1", tenant="a")
    first = q.take_batch()
    assert [r.query for r in first] == ["a0", "a1"]  # oldest tenant, FIFO
    assert [r.query for r in q.take_batch()] == ["b0"]


def test_queue_full_lane_preempts_older_partial_lane():
    now = [0.0]
    q = MicroBatchQueue(max_batch_size=2, flush_timeout_s=10.0,
                        clock=lambda: now[0])
    q.submit("a0", tenant="a")           # oldest request, lane not full
    q.submit("b0", tenant="b")
    q.submit("b1", tenant="b")           # b's lane is full
    assert q.ready() and q.next_tenant() == "b"
    assert [r.query for r in q.take_batch()] == ["b0", "b1"]
    assert not q.ready()                 # a alone, not timed out
    now[0] = 11.0
    assert q.ready()                     # a's request aged out
    assert [r.query for r in q.take_batch()] == ["a0"]


def test_queue_fairness_cap_rotates_and_only_binds_with_others_waiting():
    q = MicroBatchQueue(max_batch_size=8, flush_timeout_s=0.0,
                        fairness_cap=2)
    for i in range(6):
        q.submit(f"a{i}", tenant="a")
    q.submit("b0", tenant="b")
    assert [r.query for r in q.take_batch()] == ["a0", "a1"]  # capped at 2
    # a was just served and b is waiting: rotation skips a
    assert [r.query for r in q.take_batch()] == ["b0"]
    # a is now alone: neither the cap nor the rotation binds
    assert [r.query for r in q.take_batch()] == ["a2", "a3", "a4", "a5"]
    for i in range(5):
        q.submit(f"b{i + 1}", tenant="b")
    assert len(q.take_batch()) == 5


# --------------------------------------------------------------------------
# server: cached vs cold bit-identity (emulated shards, across tenants)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("num_shards", [2, 4, 8])
def test_server_cached_vs_cold_bit_identity_emulated_shards(num_shards):
    """Every query is submitted twice — the first pass encodes cold, the
    second is served from the query-HV cache — and both passes must be
    bit-identical to the unsharded topk_search oracle on 2/4/8 emulated
    shards (packed and unpacked encodings)."""
    rng = np.random.default_rng(100 + num_shards)
    for dim, pack in ((64, "auto"), (48, False)):
        refs = _bipolar(rng, (29, dim))
        decoys = _bipolar(rng, (28, dim))
        bank = jnp.concatenate([decoys, refs], axis=0)
        queries = np.asarray(_bipolar(rng, (10, dim)))
        reg = BankRegistry(pack=pack, emulate_shards=num_shards)
        reg.register("default", refs, decoys=decoys)
        srv = DBSearchServer(reg, k=4, fdr=1.0, max_batch_size=5,
                             flush_timeout_s=0.0, cache_bytes=1 << 20)
        oracle_idx, oracle_vals = topk_search(jnp.asarray(queries), bank, 4)
        for pass_no in range(2):
            for q in queries:
                srv.submit(q)
            done = sorted(srv.run_until_drained(), key=lambda r: r.rid)
            for i, r in enumerate(done):
                np.testing.assert_array_equal(
                    r.result.indices, np.asarray(oracle_idx)[i],
                    err_msg=f"pass={pass_no} shards={num_shards} dim={dim}")
                np.testing.assert_array_equal(
                    r.result.scores, np.asarray(oracle_vals)[i])
        qc = srv.query_cache.summary()
        assert qc["misses"] == 10 and qc["hits"] == 10  # pass 2 fully cached


def test_server_cached_vs_cold_bit_identity_across_tenants():
    """Three tenants with different bank geometries, interleaved and with
    repeated queries: each tenant's results must equal its own oracle, and
    per-tenant accounting must see the repeats as cache hits."""
    rng = np.random.default_rng(7)
    reg = BankRegistry(emulate_shards=2)
    banks, queries = {}, {}
    for t, (n_refs, n_dec) in enumerate([(20, 10), (33, 0), (13, 13)]):
        name = f"t{t}"
        refs = _bipolar(rng, (n_refs, 64))
        decoys = _bipolar(rng, (n_dec, 64)) if n_dec else None
        reg.register(name, refs, decoys=decoys)
        banks[name] = (jnp.concatenate([decoys, refs], axis=0)
                       if n_dec else refs)
        queries[name] = np.asarray(_bipolar(rng, (6, 64)))
    srv = DBSearchServer(reg, k=3, fdr=1.0, max_batch_size=4,
                         flush_timeout_s=0.0, cache_bytes=1 << 20)
    meta = {}
    for pass_no in range(2):  # second pass repeats every query -> cache hits
        for i in range(6):
            for name in banks:
                meta[srv.submit(queries[name][i], tenant=name)] = (name, i)
    done = srv.run_until_drained()
    assert len(done) == 36
    for r in done:
        name, i = meta[r.rid]
        oi, ov = topk_search(jnp.asarray(queries[name][i : i + 1]),
                             banks[name], 3)
        np.testing.assert_array_equal(r.result.indices, np.asarray(oi)[0])
        np.testing.assert_array_equal(r.result.scores, np.asarray(ov)[0])
    s = srv.summary()
    assert set(s["tenants"]) == set(banks)
    for name in banks:
        ts = s["tenants"][name]
        assert ts["count"] == 12
        assert ts["cache_hits"] == 6 and ts["cache_misses"] == 6
        assert ts["p95_ms"] >= ts["p50_ms"] >= 0.0
    assert s["banks"]["builds"] == 3 and s["banks"]["registered"] == 3


def test_server_cache_disabled_matches_cached_results():
    rng = np.random.default_rng(11)
    refs = _bipolar(rng, (24, 64))
    decoys = _bipolar(rng, (24, 64))
    queries = np.asarray(_bipolar(rng, (7, 64)))

    def run(cache_bytes):
        reg = BankRegistry(emulate_shards=4)
        reg.register("default", refs, decoys=decoys)
        srv = DBSearchServer(reg, k=4, fdr=0.5, max_batch_size=4,
                             flush_timeout_s=0.0, cache_bytes=cache_bytes)
        for q in queries:
            srv.submit(q)
        return sorted(srv.run_until_drained(), key=lambda r: r.rid)

    cold = run(None)
    cached = run(1 << 20)
    for a, b in zip(cold, cached):
        np.testing.assert_array_equal(a.result.indices, b.result.indices)
        np.testing.assert_array_equal(a.result.scores, b.result.scores)
        assert a.result.accept == b.result.accept
        assert a.result.match == b.result.match


def test_server_bucketed_dispatch_pads_to_nearest_bucket():
    rng = np.random.default_rng(13)
    refs = _bipolar(rng, (20, 64))
    db = shard_database(refs)
    srv = DBSearchServer(db, k=2, fdr=1.0, max_batch_size=8,
                         flush_timeout_s=0.0, buckets=(2, 4, 8))
    queries = np.asarray(_bipolar(rng, (7, 64)))
    oi, ov = topk_search(jnp.asarray(queries), refs, 2)
    # submit in uneven waves to force ragged flushes of 1, 3 and 3, which
    # pad to buckets 2, 4 and 4
    srv.submit(queries[0])
    done = srv.run_until_drained()
    for q in queries[1:4]:
        srv.submit(q)
    done += srv.run_until_drained()
    for q in queries[4:7]:
        srv.submit(q)
    done += srv.run_until_drained()
    assert srv.summary()["buckets"] == {2: 1, 4: 2}
    done.sort(key=lambda r: r.rid)
    for i, r in enumerate(done):
        np.testing.assert_array_equal(r.result.indices, np.asarray(oi)[i])
        np.testing.assert_array_equal(r.result.scores, np.asarray(ov)[i])


def test_server_fairness_cap_interleaves_tenants():
    rng = np.random.default_rng(17)
    reg = BankRegistry()
    reg.register("a", _bipolar(rng, (12, 64)))
    reg.register("b", _bipolar(rng, (12, 64)))
    srv = DBSearchServer(reg, k=1, fdr=1.0, max_batch_size=8,
                         flush_timeout_s=0.0, fairness_cap=2)
    qa = np.asarray(_bipolar(rng, (6, 64)))
    qb = np.asarray(_bipolar(rng, (2, 64)))
    for q in qa:
        srv.submit(q, tenant="a")
    for q in qb:
        srv.submit(q, tenant="b")
    flushes = []
    while len(srv.queue):
        batch = srv.step(force=True)
        flushes.append((batch[0].tenant, len(batch)))
    # a is capped at 2 while b waits, then rotation serves b; once a is
    # alone again the cap stops binding and it flushes the remaining 4
    assert flushes == [("a", 2), ("b", 2), ("a", 4)]
    s = srv.summary()
    assert s["tenants"]["a"]["count"] == 6
    assert s["tenants"]["b"]["count"] == 2


def test_server_submit_validates_tenant_and_shape():
    rng = np.random.default_rng(19)
    reg = BankRegistry()
    reg.register("a", _bipolar(rng, (8, 64)))
    srv = DBSearchServer(reg, k=1, max_batch_size=4)
    with pytest.raises(KeyError):
        srv.submit(np.zeros(64, np.int8), tenant="unknown")
    with pytest.raises(ValueError, match="query shape"):
        srv.submit(np.zeros(32, np.int8), tenant="a")


def test_search_database_emulated_shards_matches_oracle():
    rng = np.random.default_rng(23)
    refs = _bipolar(rng, (45, 64))
    queries = _bipolar(rng, (9, 64))
    oi, ov = topk_search(queries, refs, 5)
    for ns in (2, 4, 8):
        db = shard_database(refs, emulate_shards=ns)
        assert db.num_shards == ns
        si, sv = search_database(db, queries, 5)
        np.testing.assert_array_equal(np.asarray(si), np.asarray(oi))
        np.testing.assert_array_equal(np.asarray(sv), np.asarray(ov))


def test_shard_database_rejects_mesh_plus_emulation():
    import jax

    rng = np.random.default_rng(29)
    refs = _bipolar(rng, (8, 32))
    if len(jax.devices()) > 1:  # pragma: no cover - single-device tier-1
        pytest.skip("tier-1 is single-device")
    mesh = make_mesh((1, 1), ("data", "model"))
    # size-1 mesh axis degrades to local: emulation is then allowed
    db = shard_database(refs, mesh=mesh, emulate_shards=2)
    assert db.mesh is None and db.num_shards == 2


# --------------------------------------------------------------------------
# real multi-device multi-tenant path (slow tier)
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
def test_multi_tenant_cached_serving_on_8_device_mesh():
    """Real shard_map path: two tenants sharded over an 8-device 'model'
    axis, every query submitted twice (cold + cached), all results
    bit-identical to each tenant's unsharded oracle."""
    r = _run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.core.hd.similarity import topk_search
        from repro.serve import BankRegistry, DBSearchServer
        mesh = make_mesh((1, 8), ("data", "model"))
        rng = np.random.default_rng(3)
        reg = BankRegistry(mesh=mesh, max_banks=2)
        banks, queries = {}, {}
        for name, (R, D) in [("t0", (61, 64)), ("t1", (40, 96))]:
            refs = jnp.asarray(rng.choice([-1, 1], (R, D)).astype(np.int8))
            dec = jnp.asarray(rng.choice([-1, 1], (R // 2, D)).astype(np.int8))
            reg.register(name, refs, decoys=dec, pin=name == "t0")
            banks[name] = jnp.concatenate([dec, refs], axis=0)
            queries[name] = np.asarray(
                rng.choice([-1, 1], (8, D)).astype(np.int8))
        srv = DBSearchServer(reg, k=4, fdr=1.0, max_batch_size=4,
                             flush_timeout_s=0.0, cache_bytes=1 << 20,
                             buckets=2, fairness_cap=2)
        meta = {}
        for _ in range(2):
            for i in range(8):
                for name in banks:
                    meta[srv.submit(queries[name][i], tenant=name)] = (name, i)
        done = srv.run_until_drained()
        assert len(done) == 32, len(done)
        for r in done:
            name, i = meta[r.rid]
            oi, ov = topk_search(jnp.asarray(queries[name][i:i+1]),
                                 banks[name], 4)
            assert (r.result.indices == np.asarray(oi)[0]).all(), (name, i)
            assert (r.result.scores == np.asarray(ov)[0]).all(), (name, i)
        s = srv.summary()
        assert s["query_cache"]["hits"] == 16, s["query_cache"]
        assert s["banks"]["builds"] == 2, s["banks"]
        assert set(s["tenants"]) == {"t0", "t1"}
        print("MULTITENANT_CACHED_OK")
    """)
    assert "MULTITENANT_CACHED_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_serve_db_cli_multi_tenant_on_8_device_mesh():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve_db", "--reduced",
         "--tenants", "2", "--buckets", "2", "--cache-mb", "8",
         "--fairness-cap", "8"],
        capture_output=True, text=True, timeout=520, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "queries/sec" in r.stdout and "cache" in r.stdout, r.stdout
