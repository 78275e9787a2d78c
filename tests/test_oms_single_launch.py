"""The served OMS batch as one device program (search, overflow tail,
permutation, unsort and FDR) against the eager composition it replaced:
``oms_search_levels`` over the precursor-sorted, bucket-padded batch,
then ``fdr_route`` over its first ``n`` rows in submit order.

The bank is planted so that every case means something. Queries 0 and 1
find an exact target copy, query 2 an exact decoy copy, and query 4 both
(the decoy, a lower row, wins the tie): four rank-0 hits at the top score
whose FDR cut at 0.4 falls inside the tie, so the accepted set depends on
the order FDR sees them in. Query 6's window is empty, and query 7's
holds one row, fewer than k. A target equal to the all-absent pad row's
hypervector sits in query 4's window, where the pad rows of a 5-query
batch land: counted as valid, they would change what FDR accepts.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hd.encoding import encode_levels_batch
from repro.serve import (
    DBSearchServer,
    OMSConfig,
    QueryEncoder,
    oms_plan,
    oms_search_levels,
    shard_database,
)
from repro.serve.db_search import fdr_route

D, F, M, K, BUCKET, FDR = 64, 16, 6, 3, 8, 0.4
CFG = OMSConfig(tol=2.0, open_tol=6.0)
SENTINEL = np.iinfo(np.int32).min
# submit order; the random bank lies below 680, so windows at 700 and
# above hold only planted rows
PREC = np.array([300, 500, 150, 640, 700, 250, 1500, 800], np.float32)


def _flip(hv: np.ndarray, rng, bits: int = 4) -> np.ndarray:
    out = hv.copy()
    out[rng.choice(D, bits, replace=False)] *= -1
    return out


@pytest.fixture(scope="module")
def planted():
    rng = np.random.default_rng(2024)
    enc = QueryEncoder.from_config(dim=D, num_features=F, num_levels=M,
                                   seed=7)
    levels = rng.integers(0, M, size=(BUCKET, F)).astype(np.int32)
    hv = np.asarray(encode_levels_batch(jnp.asarray(levels), enc.id_hvs,
                                        enc.level_hvs), np.int8)
    pad_hv = -np.ones(D, np.int8)  # Eq. 1 of an all-absent level row
    targets = [hv[0], hv[1], _flip(hv[3], rng), hv[4], pad_hv]
    t_prec = [PREC[0], PREC[1], PREC[3], PREC[4], PREC[4]]
    decoys = [hv[2], hv[4], _flip(hv[5], rng), _flip(hv[7], rng)]
    d_prec = [PREC[2], PREC[4], PREC[5], PREC[7]]
    rand = rng.choice([-1, 1], size=(2, 120, D)).astype(np.int8)
    r_prec = rng.uniform(100, 680, size=(2, 120)).astype(np.float32)
    refs = np.concatenate([np.stack(targets), rand[0]])
    decs = np.concatenate([np.stack(decoys), rand[1]])
    return (enc, levels, refs, np.concatenate([t_prec, r_prec[0]]), decs,
            np.concatenate([d_prec, r_prec[1]]))


def _eager(db, enc, levels, prec, n):
    """The executor's eager composition: sort, pad, plan, search, unsort
    the first n rows, route FDR. Returns (routed, sorted rows, plan)."""
    order = np.argsort(prec, kind="stable")
    inv = np.argsort(order, kind="stable")
    padded = np.concatenate([prec[order],
                             np.full(BUCKET - n, prec[order][-1])])
    plan = oms_plan(db, padded, CFG)
    lv = np.concatenate([levels[order], np.zeros((BUCKET - n, F), np.int32)])
    idx, vals = oms_search_levels(db, enc, lv, plan, K, fused_e2e=True)
    idx, vals = np.asarray(idx), np.asarray(vals)
    routed = fdr_route(db, jnp.asarray(idx[:n][inv]),
                       jnp.asarray(vals[:n][inv]), fdr=FDR,
                       valid=jnp.asarray(plan.has_candidate[:n][inv]))
    return routed, (idx, vals, order), plan


@pytest.mark.parametrize("shards", [1, 2])
@pytest.mark.parametrize("n,straddles,pads_matter", [
    (1, False, False), (5, True, True), (BUCKET - 1, True, False),
    (BUCKET, True, False)])
def test_one_program_matches_the_eager_composition(planted, shards, n,
                                                   straddles, pads_matter):
    enc, levels, refs, t_prec, decs, d_prec = planted
    db = shard_database(jnp.asarray(refs), decoys=jnp.asarray(decs),
                        precursor=t_prec, decoy_precursor=d_prec, fused=True,
                        emulate_shards=shards if shards > 1 else None)
    server = DBSearchServer(db, k=K, fdr=FDR, max_batch_size=BUCKET,
                            oms=CFG, encoder=enc, fused_e2e=True)
    rids = [server.submit(levels[i], precursor=float(PREC[i]))
            for i in range(n)]
    got = {r.rid: r.result for r in server.run_until_drained()}
    assert server.summary()["oms"]["single_launch_batches"] == 1

    want, (idx, vals, order), plan = _eager(db, enc, levels[:n], PREC[:n], n)
    for i, rid in enumerate(rids):
        res = got[rid]
        np.testing.assert_array_equal(res.indices, want.indices[i])
        np.testing.assert_array_equal(res.scores, want.scores[i])
        assert (res.is_target, res.accept, res.match, res.has_candidate) \
            == (want.is_target[i], want.accept[i], want.match[i],
                want.valid[i]), i
    accepted = set(np.flatnonzero(want.accept))
    assert accepted == ({0} if n == 1 else {0, 1})
    if n >= 7:  # an empty window and one narrower than k
        assert not want.valid.all() and (want.scores == SENTINEL).any()

    # FDR in precursor-sorted order accepts another set: the order matters
    in_sorted = fdr_route(db, jnp.asarray(idx[:n]), jnp.asarray(vals[:n]),
                          fdr=FDR,
                          valid=jnp.asarray(plan.has_candidate[:n]))
    assert (set(order[np.flatnonzero(in_sorted.accept)]) != accepted) \
        == straddles
    # pad rows counted as valid would change the accepted set
    inv = np.argsort(order, kind="stable")
    rows = np.concatenate([inv, np.arange(n, BUCKET)])
    with_pads = fdr_route(db, jnp.asarray(idx[rows]), jnp.asarray(vals[rows]),
                          fdr=FDR,
                          valid=jnp.asarray(plan.has_candidate[rows]))
    assert (set(np.flatnonzero(with_pads.accept[:n])) != accepted) \
        == pads_matter
