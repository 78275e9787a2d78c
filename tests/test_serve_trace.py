"""The serving executor's spans and compile counter (``repro.serve.trace``)
on a tiny open-modification server: fused encode->search, continuous
batching, two slots, as the benchmark deploys it."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve import (
    DBSearchServer,
    OMSConfig,
    QueryEncoder,
    shard_database,
    trace,
)

D, F, M, K, BATCH = 64, 16, 6, 3, 8
DISPATCH_STAGES = {"serve.dispatch.plan", "serve.dispatch.assemble",
                   "serve.dispatch.launch"}
FINALIZE_STAGES = {"serve.finalize.wait", "serve.finalize.fdr",
                   "serve.finalize.results"}


def _mark() -> int:
    """The id of a span recorded now: every later span has a larger one."""
    with trace.span("test.mark"):
        pass
    return trace.spans()[-1].id


def _since(mark: int) -> list[trace.Span]:
    return [s for s in trace.spans() if s.id > mark]


@pytest.fixture(scope="module")
def server():
    rng = np.random.default_rng(0)
    refs = jnp.asarray(rng.choice([-1, 1], size=(160, D)).astype(np.int8))
    decoys = jnp.asarray(rng.choice([-1, 1], size=(160, D)).astype(np.int8))
    prec = np.sort(rng.uniform(100, 900, 160)).astype(np.float32)
    db = shard_database(refs, decoys=decoys, precursor=prec, fused=True)
    enc = QueryEncoder.from_config(dim=D, num_features=F, num_levels=M,
                                   seed=7)
    return DBSearchServer(db, k=K, max_batch_size=BATCH, continuous=True,
                          oms=OMSConfig(tol=40, open_tol=250), encoder=enc,
                          fused_e2e=True)


def _serve(server, n: int, seed: int) -> list:
    rng = np.random.default_rng(seed)
    for _ in range(n):
        server.submit(rng.integers(0, M, size=F),
                      precursor=float(rng.uniform(100, 900)))
    return server.run_until_drained()


@pytest.fixture(scope="module")
def served(server):
    """Three batches (8, 8 and 4 requests) and the spans they left."""
    mark = _mark()
    done = _serve(server, 20, seed=1)
    return done, _since(mark)


def test_each_batch_has_one_dispatch_and_one_finalize_with_stages(served):
    done, spans = served
    by_id = {s.id: s for s in spans}
    batches = {}
    for r in done:
        batches.setdefault(r.batch, []).append(r.rid)
    assert len(batches) == 3 and None not in batches
    for batch, rids in batches.items():
        for top, stages in (("serve.dispatch", DISPATCH_STAGES),
                            ("serve.finalize", FINALIZE_STAGES)):
            [parent] = [s for s in spans if s.name == top
                        and s.attrs["batch"] == batch]
            assert parent.attrs["rid0"] == min(rids)
            assert parent.parent is None
            kids = [s for s in spans if s.parent == parent.id]
            assert sorted(s.name for s in kids) == sorted(stages)
            assert all(by_id[s.parent] is parent for s in kids)
        [d] = [s for s in spans if s.name == "serve.dispatch"
               and s.attrs["batch"] == batch]
        assert d.attrs["n"] == len(rids) and d.attrs["bucket"] == BATCH
        [plan] = [s for s in spans if s.name == "serve.dispatch.plan"
                  and s.parent == d.id]
        assert plan.attrs["tiles"] >= 1


def test_children_lie_inside_their_parent_in_order(served):
    _, spans = served
    by_id = {s.id: s for s in spans}
    kids: dict[int, list] = {}
    for s in spans:
        if s.parent is not None:
            kids.setdefault(s.parent, []).append(s)
            p = by_id[s.parent]
            assert p.start <= s.start <= s.end <= p.end
    assert kids
    for stages in kids.values():
        stages.sort(key=lambda s: s.start)
        assert all(a.end <= b.start for a, b in zip(stages, stages[1:]))


def test_an_idle_step_records_nothing(server):
    mark = _mark()
    for _ in range(5):
        assert server.step() == []
    assert _since(mark) == []


def test_the_ring_stays_bounded():
    mark = _mark()
    for _ in range(trace.CAPACITY + 5):
        with trace.span("test.fill"):
            pass
    ring = trace.spans()
    assert len(ring) == trace.CAPACITY
    # the oldest went first: the mark and the first five fillers are gone
    assert ring[0].id == mark + 6 and ring[-1].name == "test.fill"


def test_spans_land_in_the_profiler_host_plane(server, tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(server, 3, seed=2)
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                    "*", "*.xplane.pb"))
    names, batches = set(), set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    names.add(e.name)
                    if e.name == "serve.dispatch":
                        batches.add(dict(e.stats)["batch"])
    assert {"serve.dispatch", "serve.finalize"} | DISPATCH_STAGES \
        | FINALIZE_STAGES <= names
    assert len(batches) == 1


def test_compiles_are_counted_by_the_open_span(server):
    jax.clear_caches()
    before = trace.compiles()
    _serve(server, 5, seed=3)
    first = trace.compiles()
    # the batch's one program compiles at its launch
    assert first.get("serve.dispatch.launch", 0) > before.get(
        "serve.dispatch.launch", 0)
    _serve(server, 5, seed=4)  # the same size again compiles nothing
    again = trace.compiles()
    assert {k: v for k, v in again.items() if k} == {
        k: v for k, v in first.items() if k}
    # a compile outside every span is counted under None
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(11))
    assert trace.compiles().get(None, 0) > again.get(None, 0)


def _oms_server(bank, *, max_batch: int) -> DBSearchServer:
    enc = QueryEncoder.from_config(dim=D, num_features=F, num_levels=M,
                                   seed=7)
    return DBSearchServer(bank, k=K, max_batch_size=max_batch,
                          continuous=True,
                          oms=OMSConfig(tol=40, open_tol=250), encoder=enc,
                          fused_e2e=True)


def _bank_rows(seed: int, rows: int):
    rng = np.random.default_rng(seed)
    hvs = [jnp.asarray(rng.choice([-1, 1], size=(rows, D)).astype(np.int8))
           for _ in range(2)]
    return hvs[0], hvs[1], rng.uniform(100, 900, rows).astype(np.float32)


def test_new_batch_sizes_in_one_bucket_compile_nothing():
    """Real sizes 3, 17 and 100 share the 128 bucket: after the first
    batch no stage of the executor compiles, and every batch took the
    one-program route. The bank fits one kernel tile, so the tile budget
    (part of the program's key) is the same for every batch."""
    refs, decoys, prec = _bank_rows(11, 60)
    server = _oms_server(shard_database(refs, decoys=decoys, precursor=prec,
                                        fused=True), max_batch=128)
    jax.clear_caches()
    _serve(server, 3, seed=5)
    first = trace.compiles()
    for n, seed in ((17, 6), (100, 7)):
        _serve(server, n, seed=seed)
    after = trace.compiles()
    assert {k: after.get(k, 0) - first.get(k, 0) for k in after
            if k and k.startswith(("serve.dispatch", "serve.finalize"))
            and after.get(k, 0) != first.get(k, 0)} == {}
    oms = server.summary()["oms"]
    assert oms["batches"] == 3 and oms["single_launch_batches"] == 3
    assert server.summary()["buckets"] == {128: 3}


def test_a_delta_tenant_takes_the_merged_route():
    """Batches against a bank with appended rows search base + delta
    merged (staged), so none of them counts as a one-program batch."""
    from repro.serve import BankRegistry
    refs, decoys, prec = _bank_rows(12, 40)
    reg = BankRegistry(fused=True)
    reg.register("default", refs, decoys=decoys, precursor=prec)
    server = _oms_server(reg, max_batch=BATCH)
    more, more_decoys, more_prec = _bank_rows(13, 4)
    server.append("default", more, more_decoys, precursor=more_prec)
    done = _serve(server, 10, seed=8)
    oms = server.summary()["oms"]
    assert len(done) == 10 and oms["batches"] == 2
    assert oms["single_launch_batches"] == 0
