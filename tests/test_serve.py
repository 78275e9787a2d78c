"""Serving subsystem tests: shard-merge bit-identity vs the unsharded
oracle (tier-1, emulated shards; slow, real 8-device shard_map), the
micro-batching queue's flush policies, and FDR routing conventions."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hd.similarity import bitpack_bipolar, topk_search, topk_search_packed
from repro.serve import (
    DBSearchServer,
    MicroBatchQueue,
    OMSConfig,
    oms_plan,
    oms_search,
    oms_search_with_fdr,
    search_database,
    search_with_fdr,
    shard_database,
    sharded_topk_search,
)
from repro.serve.queue import LatencyStats, Request

_SENTINEL = np.iinfo(np.int32).min

REPO = Path(__file__).resolve().parent.parent


def _bipolar(rng, shape):
    return jnp.asarray(rng.choice([-1, 1], size=shape).astype(np.int8))


# --------------------------------------------------------------------------
# shard-merge correctness (tier-1: emulated shards, same local/merge code)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("num_shards", [2, 4, 8])
@pytest.mark.parametrize("num_refs,dim", [
    (61, 32),   # ragged last shard at every shard count, tie-heavy low D
    (64, 64),   # exact split
    (37, 48),   # ragged + unpacked-only dim path when pack=False
])
def test_sharded_topk_matches_oracle(num_shards, num_refs, dim):
    rng = np.random.default_rng(num_refs * 100 + dim)
    refs = _bipolar(rng, (num_refs, dim))
    queries = _bipolar(rng, (16, dim))
    k = 5
    oracle_idx, oracle_vals = topk_search(queries, refs, k)
    for pack in ("auto", False):
        idx, vals = sharded_topk_search(queries, refs, k,
                                        num_shards=num_shards, pack=pack)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(oracle_idx))
        np.testing.assert_array_equal(np.asarray(vals), np.asarray(oracle_vals))


def test_sharded_topk_duplicate_rows_tiebreak():
    """Duplicated reference rows across shard boundaries force exact score
    ties; the merge must still pick the same (lowest) indices the oracle
    does."""
    rng = np.random.default_rng(7)
    base = _bipolar(rng, (12, 32))
    refs = jnp.concatenate([base, base, base], axis=0)  # 36 rows, all tied
    queries = base[:6]
    oi, ov = topk_search(queries, refs, 4)
    for ns in (2, 4, 8):
        si, sv = sharded_topk_search(queries, refs, 4, num_shards=ns)
        np.testing.assert_array_equal(np.asarray(si), np.asarray(oi))
        np.testing.assert_array_equal(np.asarray(sv), np.asarray(ov))


def test_topk_search_packed_bit_identical():
    rng = np.random.default_rng(3)
    refs = _bipolar(rng, (50, 96))
    queries = _bipolar(rng, (9, 96))
    oi, ov = topk_search(queries, refs, 6)
    pi, pv = topk_search_packed(bitpack_bipolar(queries),
                                bitpack_bipolar(refs), 96, 6)
    np.testing.assert_array_equal(np.asarray(pi), np.asarray(oi))
    np.testing.assert_array_equal(np.asarray(pv), np.asarray(ov))


def test_sharded_topk_no_shards_fallback():
    rng = np.random.default_rng(5)
    refs = _bipolar(rng, (20, 32))
    queries = _bipolar(rng, (4, 32))
    oi, ov = topk_search(queries, refs, 3)
    for kw in ({}, {"num_shards": 1}):
        si, sv = sharded_topk_search(queries, refs, 3, **kw)
        np.testing.assert_array_equal(np.asarray(si), np.asarray(oi))
        np.testing.assert_array_equal(np.asarray(sv), np.asarray(ov))


def test_single_device_database_path():
    rng = np.random.default_rng(11)
    refs = _bipolar(rng, (30, 64))
    queries = _bipolar(rng, (5, 64))
    db = shard_database(refs)
    idx, vals = search_database(db, queries, 3)
    oi, ov = topk_search(queries, refs, 3)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(oi))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(ov))


def test_shard_database_takes_packed_blocks():
    """Already bit-packed refs/decoys (the chunked bank encoder's output)
    build the same bank as bipolar HVs, exact and precursor-sorted."""
    rng = np.random.default_rng(41)
    refs, dec = _bipolar(rng, (37, 64)), _bipolar(rng, (21, 64))
    prec = rng.uniform(400, 1600, 37).astype(np.float32)
    for kw in ({}, {"precursor": prec, "decoy_precursor": prec[:21]}):
        a = shard_database(refs, decoys=dec, **kw)
        b = shard_database(bitpack_bipolar(refs),
                           decoys=bitpack_bipolar(dec), **kw)
        assert b.packed and b.dim == 64 and b.num_decoys == 21
        np.testing.assert_array_equal(np.asarray(a.data), np.asarray(b.data))
    with pytest.raises(ValueError, match="decoy dim"):
        shard_database(bitpack_bipolar(refs), decoys=dec)


def test_k_exceeding_shard_rows_raises():
    rng = np.random.default_rng(13)
    refs = _bipolar(rng, (8, 32))
    queries = _bipolar(rng, (2, 32))
    with pytest.raises(ValueError, match="shard_rows"):
        sharded_topk_search(queries, refs, 5, num_shards=4)
    db = shard_database(refs)
    with pytest.raises(ValueError, match="bank rows"):
        search_database(db, queries, 9)


# --------------------------------------------------------------------------
# FDR routing
# --------------------------------------------------------------------------

def test_fdr_route_accepts_clear_target_hits():
    rng = np.random.default_rng(17)
    refs = _bipolar(rng, (40, 128))
    decoys = _bipolar(rng, (40, 128))
    db = shard_database(refs, decoys=decoys)
    res = search_with_fdr(db, refs[:10], k=4, fdr=0.05)
    # querying exact library rows: every hit is its own row, all accepted
    np.testing.assert_array_equal(res.match, np.arange(10))
    assert res.accept.all() and res.is_target.all()
    # indices are bank rows: targets live after the decoy block
    assert (res.indices[:, 0] == np.arange(10) + db.num_decoys).all()


def test_fdr_route_tie_resolves_to_decoy():
    """A target/decoy exact score tie must lose the competition (the
    conservative best_target > best_decoy convention): decoys precede
    targets in the bank, so the tied decoy wins rank 0."""
    rng = np.random.default_rng(19)
    row = _bipolar(rng, (1, 32))
    refs = jnp.concatenate([row, _bipolar(rng, (5, 32))], axis=0)
    decoys = jnp.concatenate([row, _bipolar(rng, (5, 32))], axis=0)
    db = shard_database(refs, decoys=decoys)
    res = search_with_fdr(db, row, k=3, fdr=1.0)
    assert not res.is_target[0]
    assert res.match[0] == -1


# --------------------------------------------------------------------------
# open-modification search: banded/sharded routes vs the masked oracle
# --------------------------------------------------------------------------

def _oms_oracle(db, q, sorted_bank, plan, k):
    """Sentinel-mask the full score matrix over the *sorted* bank outside
    the plan's bands, run lax.top_k, translate winners through the
    permutation — the definition oms_search_encoded must match bit-exactly,
    tie order and overflow slots included."""
    scores = q.astype(jnp.int32) @ sorted_bank.T.astype(jnp.int32)
    col = jnp.arange(sorted_bank.shape[0], dtype=jnp.int32)[None, :]
    band = jnp.zeros(scores.shape, bool)
    starts = jnp.asarray(plan.starts)
    ends = starts + jnp.asarray(plan.lens)
    for b in range(starts.shape[0]):
        band = band | ((col >= starts[b][:, None]) & (col < ends[b][:, None]))
    scores = jnp.where(band, scores, _SENTINEL)
    vals, idx = jax.lax.top_k(scores, k)
    return jnp.take(jnp.asarray(db.oms.perm), idx, axis=0), vals


def _oms_fixture(rng, *, num_refs=150, dim=64, num_queries=23):
    refs = _bipolar(rng, (num_refs, dim))
    decoys = _bipolar(rng, (num_refs, dim))
    prec = rng.uniform(400, 1600, num_refs).astype(np.float32)
    qprec = rng.uniform(420, 1650, num_queries).astype(np.float32)
    queries = _bipolar(rng, (num_queries, dim))
    return refs, decoys, prec, queries, qprec


@pytest.mark.parametrize("num_shards", [1, 2, 4, 8])
@pytest.mark.parametrize("fused", [False, True])
def test_oms_search_bit_identical_to_masked_oracle(num_shards, fused):
    """Every OMS route (banded kernel or masked unfused, any emulated shard
    count, packed or int8 banks) must equal sentinel-masking the full score
    matrix over the sorted bank and translating through the permutation."""
    rng = np.random.default_rng(num_shards * 10 + fused)
    refs, decoys, prec, queries, qprec = _oms_fixture(rng)
    cfg = OMSConfig(tol=15.0, open_tol=150.0)
    k = 7
    for pack in ("auto", False):
        db = shard_database(refs, decoys=decoys, pack=pack, fused=fused,
                            emulate_shards=(num_shards if num_shards > 1
                                            else None),
                            precursor=prec)
        plan = oms_plan(db, qprec, cfg)
        idx, vals, _ = oms_search(db, queries, qprec, k, cfg)
        sorted_bank = jnp.concatenate([decoys, refs])[jnp.asarray(db.oms.perm)]
        oi, ov = _oms_oracle(db, queries, sorted_bank, plan, k)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(oi),
                                      err_msg=str((num_shards, fused, pack)))
        np.testing.assert_array_equal(np.asarray(vals), np.asarray(ov),
                                      err_msg=str((num_shards, fused, pack)))


def test_oms_fused_equals_unfused_through_fdr():
    rng = np.random.default_rng(41)
    refs, decoys, prec, queries, qprec = _oms_fixture(rng, num_refs=90)
    res = {}
    for fused in (False, True):
        db = shard_database(refs, decoys=decoys, emulate_shards=4,
                            fused=fused, precursor=prec)
        res[fused] = oms_search_with_fdr(db, queries, qprec, k=4, fdr=0.5)
    np.testing.assert_array_equal(res[True].indices, res[False].indices)
    np.testing.assert_array_equal(res[True].scores, res[False].scores)
    np.testing.assert_array_equal(res[True].accept, res[False].accept)
    np.testing.assert_array_equal(res[True].match, res[False].match)


def test_oms_empty_window_rejected_not_counted_as_decoy():
    """A query whose precursor window is empty must come back rejected
    (match -1, valid False) without depressing the FDR acceptance of the
    rest of the batch."""
    rng = np.random.default_rng(43)
    refs = _bipolar(rng, (40, 64))
    decoys = _bipolar(rng, (40, 64))
    prec = rng.uniform(400, 1600, 40).astype(np.float32)
    db = shard_database(refs, decoys=decoys, precursor=prec)
    queries = jnp.concatenate([refs[:6], _bipolar(rng, (3, 64))])
    qprec = np.concatenate([prec[:6], np.full(3, 1e6, np.float32)])
    res = oms_search_with_fdr(db, queries, qprec, k=3, fdr=0.05)
    assert res.valid is not None
    np.testing.assert_array_equal(np.asarray(res.valid),
                                  [True] * 6 + [False] * 3)
    assert (res.match[6:] == -1).all() and not res.accept[6:].any()
    assert not res.is_target[6:].any()
    # exact library rows with a clean window: all six accepted
    assert res.accept[:6].all()


def test_oms_requires_precursor_bank():
    rng = np.random.default_rng(47)
    refs = _bipolar(rng, (20, 32))
    db = shard_database(refs)  # no precursor=
    with pytest.raises(ValueError, match="precursor"):
        oms_plan(db, np.asarray([500.0], np.float32))


def test_oms_server_matches_direct_search():
    """One OMS server flush == the direct oms_search_with_fdr call on the
    same queries: the server's precursor sort/unsort and padding must be
    invisible in the results."""
    rng = np.random.default_rng(53)
    refs, decoys, prec, queries, qprec = _oms_fixture(
        rng, num_refs=60, num_queries=8)
    db = shard_database(refs, decoys=decoys, precursor=prec)
    cfg = OMSConfig(tol=15.0, open_tol=150.0)
    srv = DBSearchServer(db, k=3, fdr=0.5, max_batch_size=8,
                         flush_timeout_s=0.0, oms=cfg)
    for q, p in zip(np.asarray(queries), qprec):
        srv.submit(q, precursor=float(p))
    done = srv.run_until_drained()
    direct = oms_search_with_fdr(db, queries, qprec, k=3, fdr=0.5, cfg=cfg)
    assert len(done) == 8
    for i, r in enumerate(done):
        np.testing.assert_array_equal(r.result.indices, direct.indices[i])
        np.testing.assert_array_equal(r.result.scores, direct.scores[i])
        assert r.result.accept == bool(direct.accept[i])
        assert r.result.match == int(direct.match[i])
        assert r.result.has_candidate == bool(direct.valid[i])
    oms_stats = srv.summary()["oms"]
    assert oms_stats["batches"] == 1
    assert 0.0 < oms_stats["candidate_fraction"] < 1.0


def test_oms_server_ragged_flush_padding_is_invisible():
    """A ragged OMS flush (n < max_batch_size) pads queries *and*
    precursors; padded rows must not perturb the real results."""
    rng = np.random.default_rng(59)
    refs, decoys, prec, queries, qprec = _oms_fixture(
        rng, num_refs=60, num_queries=3)
    db = shard_database(refs, decoys=decoys, precursor=prec)
    srv = DBSearchServer(db, k=3, fdr=0.5, max_batch_size=8,
                         flush_timeout_s=0.0, oms=OMSConfig())
    for q, p in zip(np.asarray(queries), qprec):
        srv.submit(q, precursor=float(p))
    done = srv.run_until_drained()
    direct = oms_search_with_fdr(db, queries, qprec, k=3, fdr=0.5,
                                 cfg=OMSConfig())
    for i, r in enumerate(done):
        np.testing.assert_array_equal(r.result.indices, direct.indices[i])
        assert r.result.match == int(direct.match[i])


def test_oms_server_submit_without_precursor_raises():
    rng = np.random.default_rng(61)
    refs = _bipolar(rng, (20, 32))
    prec = rng.uniform(400, 1600, 20).astype(np.float32)
    db = shard_database(refs, precursor=prec)
    srv = DBSearchServer(db, k=2, max_batch_size=4, oms=OMSConfig())
    with pytest.raises(ValueError, match="precursor"):
        srv.submit(np.asarray(refs[0]))


# --------------------------------------------------------------------------
# micro-batching queue
# --------------------------------------------------------------------------

def test_queue_flushes_on_max_batch():
    now = [0.0]
    q = MicroBatchQueue(max_batch_size=3, flush_timeout_s=10.0,
                        clock=lambda: now[0])
    assert not q.ready()
    q.submit("a"), q.submit("b")
    assert not q.ready()                      # 2 < max, nothing timed out
    q.submit("c")
    assert q.ready()                          # full batch, no time passed
    batch = q.take_batch()
    assert [r.query for r in batch] == ["a", "b", "c"]  # FIFO
    assert len(q) == 0 and not q.ready()


def test_queue_flushes_on_timeout():
    now = [100.0]
    q = MicroBatchQueue(max_batch_size=64, flush_timeout_s=0.5,
                        clock=lambda: now[0])
    q.submit("only")
    assert not q.ready()
    assert q.time_until_flush() == pytest.approx(0.5)
    now[0] += 0.49
    assert not q.ready()
    now[0] += 0.02
    assert q.ready() and q.time_until_flush() == 0.0
    assert [r.query for r in q.take_batch()] == ["only"]


def test_queue_take_batch_caps_at_max_and_keeps_fifo():
    q = MicroBatchQueue(max_batch_size=4, flush_timeout_s=0.0)
    rids = [q.submit(i) for i in range(10)]
    first = q.take_batch()
    assert [r.rid for r in first] == rids[:4]
    assert len(q) == 6
    assert [r.rid for r in q.take_batch()] == rids[4:8]


def test_latency_stats_percentiles():
    now = [0.0]
    stats = LatencyStats()
    reqs = []
    for i in range(10):
        reqs.append(Request(rid=i, query=None, t_submit=float(i),
                            t_done=float(i) + (i + 1) * 0.01))
    stats.record_batch(reqs)
    s = stats.summary()
    assert s["count"] == 10 and s["batches"] == 1
    assert s["p50_ms"] == pytest.approx(55.0)
    assert s["p95_ms"] == pytest.approx(95.5)
    del now


def test_latency_stats_bounded_window():
    stats = LatencyStats(window=4)
    reqs = [Request(rid=i, query=None, t_submit=float(i),
                    t_done=float(i) + 0.1 * (i + 1)) for i in range(10)]
    for r in reqs:
        stats.record_batch([r])
    s = stats.summary()
    assert s["count"] == 10 and s["batches"] == 10  # exact running totals
    assert len(stats._latencies) == 4               # bounded memory
    # percentiles over the latest window only (latencies 0.7..1.0)
    assert s["p50_ms"] == pytest.approx(850.0)


# --------------------------------------------------------------------------
# server loop
# --------------------------------------------------------------------------

def _make_server(rng, clock, **kw):
    refs = _bipolar(rng, (24, 64))
    decoys = _bipolar(rng, (24, 64))
    db = shard_database(refs, decoys=decoys)
    return refs, DBSearchServer(db, clock=clock, **kw)


def test_server_flush_on_batch_and_timeout():
    now = [0.0]
    rng = np.random.default_rng(23)
    refs, srv = _make_server(rng, lambda: now[0], k=3, fdr=1.0,
                             max_batch_size=4, flush_timeout_s=1.0)
    for i in range(3):
        srv.submit(np.asarray(refs[i]))
    assert srv.step() == []                   # 3 < max batch, no timeout
    srv.submit(np.asarray(refs[3]))
    done = srv.step()                         # flush on max batch
    assert [r.rid for r in done] == [0, 1, 2, 3]
    srv.submit(np.asarray(refs[4]))
    assert srv.step() == []
    now[0] += 1.5
    done = srv.step()                         # flush on timeout
    assert [r.rid for r in done] == [4]
    assert done[0].latency_s == pytest.approx(1.5)


def test_server_padded_batch_matches_direct_search():
    """A ragged flush (n < max_batch_size) is padded for a single jit
    signature; results must equal searching exactly those queries."""
    now = [0.0]
    rng = np.random.default_rng(29)
    refs = _bipolar(rng, (32, 64))
    decoys = _bipolar(rng, (32, 64))
    db = shard_database(refs, decoys=decoys)
    srv = DBSearchServer(db, k=4, fdr=0.5, max_batch_size=8,
                         flush_timeout_s=0.0, clock=lambda: now[0])
    queries = _bipolar(rng, (3, 64))
    for q in np.asarray(queries):
        srv.submit(q)
    done = srv.run_until_drained()
    direct = search_with_fdr(db, queries, k=4, fdr=0.5)
    for i, r in enumerate(done):
        np.testing.assert_array_equal(r.result.indices, direct.indices[i])
        np.testing.assert_array_equal(r.result.scores, direct.scores[i])
        assert r.result.accept == bool(direct.accept[i])
        assert r.result.match == int(direct.match[i])


def test_serve_db_cli_single_device():
    from repro.launch import serve_db
    s = serve_db.main(["--reduced", "--hd-dim", "64", "--identities", "8",
                       "--queries", "16", "--max-batch", "4",
                       "--k", "2", "--fdr", "0.5"])
    assert s["count"] > 0 and s["qps"] > 0


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
def test_sharded_search_bit_identical_on_8_device_mesh():
    r = _run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.core.hd.similarity import topk_search
        from repro.serve import shard_database, search_database
        rng = np.random.default_rng(1)
        for model_n in (2, 4, 8):
            mesh = make_mesh((8 // model_n, model_n), ("data", "model"))
            for R, D in [(61, 32), (64, 64), (37, 48)]:
                refs = jnp.asarray(rng.choice([-1, 1], (R, D)).astype(np.int8))
                q = jnp.asarray(rng.choice([-1, 1], (16, D)).astype(np.int8))
                oi, ov = topk_search(q, refs, 4)
                for pack in ([True, False] if D % 32 == 0 else [False]):
                    for fused in (False, True):
                        db = shard_database(refs, mesh=mesh, pack=pack,
                                            fused=fused)
                        si, sv = search_database(db, q, 4)
                        assert (np.asarray(si) == np.asarray(oi)).all(), (model_n, R, D, pack, fused)
                        assert (np.asarray(sv) == np.asarray(ov)).all(), (model_n, R, D, pack, fused)
        print("SHARDED_TOPK_OK")
    """)
    assert "SHARDED_TOPK_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_oms_search_bit_identical_on_8_device_mesh():
    """Real shard_map OMS routes (scalar bands broadcast via the in_specs,
    banded kernel per shard) vs the single-device masked path."""
    r = _run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.serve import OMSConfig, oms_search, shard_database
        rng = np.random.default_rng(2)
        R, D, Q, k = 150, 64, 16, 5
        refs = jnp.asarray(rng.choice([-1, 1], (R, D)).astype(np.int8))
        decoys = jnp.asarray(rng.choice([-1, 1], (R, D)).astype(np.int8))
        prec = rng.uniform(400, 1600, R).astype(np.float32)
        q = jnp.asarray(rng.choice([-1, 1], (Q, D)).astype(np.int8))
        qprec = np.sort(rng.uniform(420, 1650, Q).astype(np.float32))
        cfg = OMSConfig(tol=15.0, open_tol=150.0)
        ref_db = shard_database(refs, decoys=decoys, precursor=prec)
        oi, ov, _ = oms_search(ref_db, q, qprec, k, cfg)
        for model_n in (2, 4, 8):
            mesh = make_mesh((8 // model_n, model_n), ("data", "model"))
            for pack in (True, False):
                for fused in (False, True):
                    db = shard_database(refs, decoys=decoys, mesh=mesh,
                                        pack=pack, fused=fused,
                                        precursor=prec)
                    si, sv, _ = oms_search(db, q, qprec, k, cfg)
                    assert (np.asarray(si) == np.asarray(oi)).all(), (model_n, pack, fused)
                    assert (np.asarray(sv) == np.asarray(ov)).all(), (model_n, pack, fused)
        print("OMS_SHARDED_OK")
    """)
    assert "OMS_SHARDED_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_serve_db_cli_on_8_device_mesh():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve_db", "--reduced"],
        capture_output=True, text=True, timeout=520, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "queries/sec" in r.stdout and "p50" in r.stdout, r.stdout
