"""The one place the benchmark reads the program's own spans.

The serving executor records a ``serve.dispatch`` and a
``serve.finalize`` span for every batch in the process-wide ring of
``repro.serve.trace``, each carrying the batch's first request id
(``rid0``) and holding one child span per stage. A stage's time is its
mean duration over the window's batches, the very batches
``executor_ms`` averages over, joined to them by ``rid0``. Where the
program keeps no such ring, or the ring has dropped the stage's span of
any window batch, there is nothing to read and the reader returns None.
"""

from __future__ import annotations

import collections

TOPS = ("serve.dispatch", "serve.finalize")


def stage_ms(rec, name: str) -> float | None:
    """Mean duration (ms) of span ``name`` per window batch, or None."""
    try:
        from repro.serve import trace
    except ImportError:
        return None
    want = {b.rids[0] for b in rec.window_batches}
    if not want:
        return None
    # every window batch was dispatched after the window opened; an
    # earlier run in the same process used the same request ids before it
    spans = [s for s in trace.spans() if s.start >= rec.t0]
    by_id = {s.id: s for s in spans}
    per_batch: dict[int, float] = collections.defaultdict(float)
    for s in spans:
        if s.name != name:
            continue
        top = by_id.get(s.parent, s)  # stages nest one level deep
        rid0 = top.attrs.get("rid0") if top.name in TOPS else None
        if rid0 in want:
            per_batch[rid0] += s.end - s.start
    if len(per_batch) < len(want):
        return None
    return sum(per_batch.values()) / len(want) * 1e3


def batch_tiles(rec) -> list[tuple[int, int]] | None:
    """(requests, OMS tile budget) of each window batch, in dispatch
    order, from the ``serve.dispatch.plan`` spans; None where any is
    missing."""
    try:
        from repro.serve import trace
    except ImportError:
        return None
    spans = [s for s in trace.spans() if s.start >= rec.t0]
    by_id = {s.id: s for s in spans}
    tiles = {}
    for s in spans:
        top = by_id.get(s.parent)
        if s.name == "serve.dispatch.plan" and top is not None:
            tiles[top.attrs.get("rid0")] = s.attrs.get("tiles")
    out = [(len(b.rids), tiles.get(b.rids[0])) for b in rec.window_batches]
    if any(t is None for _, t in out):
        return None
    return out
