"""The readers of the program's own spans, through the harness on the CPU:
the tiny backlog cell, traced, reports each stage, each stage fits inside
the harness's own timing of the same batches, and a window whose first
batch the ring has dropped reads nothing."""

import run
from test_harness import result, root  # noqa: F401  (root is a fixture)

from repro.serve import trace

STAGES = ("dispatch_ms.offline", "plan_ms.offline",
          "search_wait_ms.offline", "fdr_ms.offline")


def test_stages_are_read_and_fit_the_harness_spans(capsys, root,  # noqa: F811
                                                   monkeypatch):
    seen = {}
    real = run.load_reader

    def spy(root_, metric):
        read = real(root_, metric)

        def keep(rec):
            seen["rec"] = rec
            return read(rec)
        return keep

    monkeypatch.setattr(run, "load_reader", spy)
    res, _ = result(capsys, root, "tiny.backlog", trace=1)
    assert res["correct"]
    got = {m: res["metrics"][m]["value"] for m in STAGES}
    assert all(v > 0 for v in got.values()), got

    batches = seen["rec"].window_batches
    dispatch = sum(b.dispatch_s for b in batches) / len(batches) * 1e3
    finalize = sum(b.finalize_s for b in batches) / len(batches) * 1e3
    assert got["plan_ms.offline"] < got["dispatch_ms.offline"] <= dispatch
    assert (got["search_wait_ms.offline"] + got["fdr_ms.offline"]
            <= finalize)

    # drop spans from the ring until the window's first batch is gone
    rec, first = seen["rec"], batches[0].rids[0]
    ring = trace.spans()
    last = max(i for i, s in enumerate(ring) if s.name == "serve.finalize"
               and s.attrs["rid0"] == first and s.start >= rec.t0)
    for _ in range(trace.CAPACITY - len(ring) + last + 1):
        with trace.span("test.fill"):
            pass
    for m in STAGES:
        assert real(root, m)(rec) is None
