"""The open loop's readers, its warm-up plan, its query order and the
iPRG2012 configuration, without a run."""

import dataclasses
import json
import types
from pathlib import Path

import numpy as np
import pytest

import gen
import loadgen
import readers

BENCH = Path(__file__).resolve().parents[1]


def record(batches, served, due):
    return types.SimpleNamespace(
        window_batches=batches, served=served, due=due, attempted=list(due),
        config={"serving": {"max_batch_size": 4}})


def test_open_loop_readers_read_nothing_without_window_batches():
    rec = record([], {}, {})
    assert readers.batch_fill(rec) is None
    assert readers.queue_wait_p95_ms(rec) is None


def test_open_loop_readers():
    due = {r: 10.0 for r in range(5)}
    served = {r: types.SimpleNamespace(t_dispatch=10.0 + 0.001 * r)
              for r in range(4)}  # request 4 never answered
    rec = record([types.SimpleNamespace(rids=[0, 1, 2, 3]),
                  types.SimpleNamespace(rids=[4])], served, due)
    assert readers.batch_fill(rec) == pytest.approx(5 / 8)
    assert readers.queue_wait_p95_ms(rec) == pytest.approx(3.0)


def test_iprg2012_config_is_the_paper_library_unreduced():
    cfg = json.loads((BENCH / "configs" / "iprg2012.json").read_text())
    spec = gen.Spec.from_config(cfg)
    assert spec.library_rows == cfg["library_spectra"] == 1_162_392
    assert 2 * spec.library_rows == 2_324_784  # targets and decoys
    assert cfg["reduced"] == []
    win = cfg["windows"]["open"]
    span = spec.precursor_range[1] - spec.precursor_range[0]
    assert (win["tol"] + win["open_tol"]) / span == pytest.approx(0.025)


def test_open_loop_warm_up_spans_every_spread_in_each_bucket():
    pool = types.SimpleNamespace(levels=np.zeros((16, 3), np.uint8),
                                 precursor=np.zeros(16, np.float32))
    sweeps = loadgen.warm_batches({"arrival": "poisson"}, 16, (8, 16), pool,
                                  400.0, 1600.0)
    assert len(sweeps) == loadgen.WARM_SWEEPS
    batches = sweeps[0]
    assert len(batches) == 2 * len(loadgen.SPREADS)
    widths = [float(p.max() - p.min()) for _, p in batches]
    assert max(widths) == pytest.approx(1200.0) and min(widths) == 0.0
    # successive spreads at most sqrt(2) apart, so no power of two of the
    # planner's span is skipped
    w = sorted(x for x in widths[:len(loadgen.SPREADS)] if x > 0)
    assert max(b / a for a, b in zip(w, w[1:])) <= 2 ** 0.5 * (1 + 1e-4)
    assert all(p.min() == 400.0 for _, p in batches)
    assert {len(lv) for lv, _ in batches} == {8, 16}


def test_backlog_warm_up_sends_fresh_full_batches():
    n = 4
    size = loadgen.warm_pool_size({"arrival": "backlog"}, n)
    pool = types.SimpleNamespace(levels=np.arange(size)[:, None],
                                 precursor=np.arange(size, dtype=np.float32))
    sweeps = loadgen.warm_batches({"arrival": "backlog"}, n, (n,), pool,
                                  0.0, 0.0)
    rows = [int(r) for s in sweeps for lv, p in s for r in lv[:, 0]]
    assert rows == list(range(size))
    assert all(len(lv) == n and np.array_equal(lv[:, 0], p)
               for s in sweeps for lv, p in s)


def test_golden_order_spreads_every_run_of_consecutive_requests(tiny_cfg):
    """Whatever n consecutive requests make a batch, their precursors leave
    no gap wider than 2/n of the range, plus a modification's shift."""
    spec = dataclasses.replace(gen.Spec.from_config(tiny_cfg),
                               templates=gen.CHUNK)
    table = gen.template_table(spec, gen.seed_key(2**31 + 7, 2))
    lo, hi = spec.precursor_range
    shift = spec.modification_mass[1] + 1.0
    a = gen.query_pool(spec, 2**31 + 7, 10, 600, table, order="golden")
    for n in (8, 32, 128):
        for i in range(0, len(a) - n, 13):
            p = np.sort(a.precursor[i:i + n])
            assert np.diff(p).max() < 2 * (hi - lo) / n + shift
    b = gen.query_pool(spec, 2**31 + 8, 10, 600, table, order="golden")
    assert not np.allclose(np.sort(a.precursor), np.sort(b.precursor))
    with pytest.raises(ValueError):
        gen.query_pool(spec, 1, 10, 8, table, order="sorted")


def test_random_order_sends_one_batch_set_in_each_seeds_order(tiny_cfg):
    """Every seed sends the same random batches, each whole in an aligned
    run, and in the same order, which is the set's own: every cycle of the
    set takes an order of its own drawn from the set's seed. A batch is a
    set of precursors with their modifications; with the library's
    precursors from the set's seed too, a seed moves the spectra and the
    order of a batch's members, not its precursors."""
    spec = dataclasses.replace(gen.Spec.from_config(tiny_cfg),
                               templates=gen.CHUNK)
    n, sets = 16, 4

    def table(seed, set_seed=3):
        return gen.template_table(spec, gen.seed_key(seed, 2),
                                  gen.seed_key(set_seed, 2))

    def pool(seed, set_seed=3):
        return gen.query_pool(spec, seed, 10, 3 * n * sets,
                              table(seed, set_seed), n, order="random",
                              set_batches=sets, set_seed=set_seed)

    def batches(p):
        return np.sort(p.precursor.reshape(-1, n), axis=1)

    def same_set(x, y):
        """Each batch of x is one of y, and no two are the same one."""
        hit = [[np.array_equal(u, v) for v in y] for u in x]
        return all(map(any, hit)) and len({r.index(True) for r in hit}) == len(x)

    pa, pb = pool(2**31 + 7), pool(2**31 + 8)
    a, b = batches(pa), batches(pb)
    np.testing.assert_array_equal(a, b)  # the same batches, in one order
    assert not np.array_equal(pa.precursor, pb.precursor)  # members shuffled
    assert not np.array_equal(pa.levels, pb.levels)  # fresh spectra
    for cyc in range(1, 3):
        assert same_set(a[cyc * sets:(cyc + 1) * sets], a[:sets])
    assert not np.array_equal(a[:sets], a[sets:2 * sets])  # another order
    # another set: other batches
    d = batches(pool(2**31 + 7, set_seed=4))
    assert not same_set(d[:sets], a[:sets])
    # random, not spread evenly: some batch leaves a gap far wider than
    # an even split of the range would
    lo, hi = spec.precursor_range
    assert np.diff(a, axis=1).max() > 3 * (hi - lo) / n


def test_a_precursor_seed_fixes_the_librarys_precursors(tiny_cfg):
    """``precursor_seed`` draws every library precursor, the seed the
    spectra: two seeds then share the precursor layout, and the seed alone
    draws precursors of its own."""
    spec = gen.Spec.from_config(tiny_cfg)
    a, _ = gen.build_library(spec, 2**31 + 11, precursor_seed=3)
    b, _ = gen.build_library(spec, 2**31 + 12, precursor_seed=3)
    c, _ = gen.build_library(spec, 2**31 + 12)
    np.testing.assert_array_equal(a.precursor, b.precursor)
    assert not np.array_equal(a.targets, b.targets)
    np.testing.assert_array_equal(b.targets, c.targets)
    assert not np.allclose(b.precursor, c.precursor)


def test_random_order_backlog_warms_every_tile_budget():
    mix = {"arrival": "backlog", "precursor_order": "random"}
    assert loadgen.warm_pool_size(mix, 16) == 16
    pool = types.SimpleNamespace(levels=np.zeros((16, 3), np.uint8),
                                 precursor=np.zeros(16, np.float32))
    sweeps = loadgen.warm_batches(mix, 16, (16,), pool, 400.0, 1600.0)
    assert len(sweeps[0]) == len(loadgen.SPREADS)
