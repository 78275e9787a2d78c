"""The window rule and the work count, against brute force and by hand."""

import numpy as np
import pytest
import work

PEAKS = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


def test_window_rule_matches_a_brute_force_mask():
    rng = np.random.default_rng(0)
    prec = np.sort(rng.uniform(400, 1600, 5000).astype(np.float32))
    # queries on and next to row masses, so both strict bounds are hit
    q = np.concatenate([prec[::50] + 2.5, prec[::50] - 28.0,
                        rng.uniform(380, 1620, 200)]).astype(np.float32)
    s, e = work.window_ranges(prec, q, 2.5, 28.0)
    for i, qi in enumerate(q):
        mask = (prec > qi - np.float32(28.0)) & (prec < qi + np.float32(2.5))
        assert np.flatnonzero(mask).tolist() == list(range(s[i], e[i]))


def test_union_length():
    s = np.array([0, 5, 30, 12, 50])
    e = np.array([10, 20, 40, 15, 50])
    assert work.union_length(s, e) == 20 + 10


def test_batch_work_by_hand():
    prec = np.arange(100, dtype=np.float32) * 10 + 400  # 400, 410, ...
    q = np.array([405.0, 415.0], np.float32)
    # window (q - 28, q + 2.5): 405 -> rows 380..405 => 400 (1 row);
    # 415 -> 390..415 => 400, 410 (2 rows); union 2 rows
    ops, nbytes = work.batch_work(prec, q, dim=8192, num_bins=1024,
                                  tol=2.5, open_tol=28.0)
    assert ops == 2 * 8192 * 2 * (1 + 2) + 2 * 1024 * 8192 * 2
    assert nbytes == 8192 // 8 * 2 * 2 + 1024 * 2
    t, bound = work.least_time(ops, nbytes, PEAKS)
    assert bound == "ops" and t == pytest.approx(ops / 393e12)


def test_work_count_is_the_same_on_the_fused_and_staged_routes(tiny_lib):
    """One batch served by the fused encode->search route and by the staged
    encode-then-search route is counted alike: the count reads the
    precursors, never the route."""
    import adapter
    lib, pool, cfg = tiny_lib
    win = cfg["windows"]["open"]
    counts = []
    for fused in (True, False):
        dep = adapter.Deployment(lib, cfg["serving"], win, fused_e2e=fused)
        rids = [dep.submit(pool.levels[i], pool.precursor[i])
                for i in range(8)]
        while dep.pending():
            dep.step()
        (batch,) = dep.batches
        assert batch.rids == rids
        prec = np.array([pool.precursor[rids.index(r)] for r in batch.rids])
        counts.append(work.batch_work(
            np.sort(lib.precursor), prec, dim=cfg["dim"],
            num_bins=cfg["num_bins"], **win))
    assert counts[0] == counts[1]


def test_peaks_are_known_for_the_v5e_and_refused_for_others():
    import run
    v5e = run.load_peaks("TPU v5 lite")
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        run.load_peaks("TPU v9 imaginary")


def test_every_batch_of_the_pool_spans_the_precursor_range_alike(tiny_cfg):
    """With the modifications off, the 16 queries of each run come from 16
    different sixteenths of the templates ranked by precursor."""
    import dataclasses

    import gen
    spec = dataclasses.replace(gen.Spec.from_config(tiny_cfg),
                               modification_rate=0.0)
    table = gen.template_table(spec, gen.seed_key(2**31 + 9, 2))
    tprec = np.sort(np.asarray(table[2])[:spec.templates])
    pool = gen.query_pool(spec, 2**31 + 9, 10, 64, table, strata=16)
    ranks = np.abs(pool.precursor[:, None] - tprec[None, :]).argmin(axis=1)
    slices = ranks * 16 // spec.templates
    for run in slices.reshape(-1, 16):
        assert sorted(run.tolist()) == list(range(16))
