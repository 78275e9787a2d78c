"""The one load generator: it reads a traffic mix (``bench/traffic/*.json``)
and drives the deployment from a single thread.

Arrival processes:

* ``poisson`` — an open loop at ``rate_per_s``. A run of S seconds offers
  exactly round(rate * S) requests, due at sorted uniform times over the
  window: a Poisson process conditioned on its count, so every seed offers
  the same amount of work. Each request's latency runs from when it was
  due, so a late generator or a stalled server shows in the tail.
* ``backlog`` — a batch job: the queue is topped up to
  ``backlog_batches`` full batches whenever it falls below. When the
  window's time is up nothing more is sent, what is still queued is
  cancelled, every dispatched batch is waited for, and the clock is read
  after that wait: the rate is all dispatched spectra over all that time.

Every request is a distinct spectrum from the run's pool; none is sent
twice.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

IDLE_SLEEP_S = 0.0002  # loop pause when nothing is due and nothing finished
DRAIN_LIMIT_S = 60.0   # how long answers due in the window are waited for


@dataclasses.dataclass
class Window:
    t0: float
    t_end: float        # nothing is sent from here on
    due: dict           # rid -> due time (host clock)
    pool_row: dict      # rid -> row of the query pool
    served: dict        # rid -> adapter.Served
    attempted: list     # rids whose answers are due
    t_close: float = 0.0  # backlog: when the last dispatched answer came


def pool_size(mix: dict, seconds: float) -> int:
    if mix["arrival"] == "poisson":
        return int(round(mix["rate_per_s"] * seconds))
    if mix["arrival"] == "backlog":
        return int(mix["pool"])
    raise ValueError(f"unknown arrival process {mix['arrival']!r}")


def warm_sizes(mix: dict, max_batch: int) -> list[int]:
    """Batch sizes of one warm-up sweep: the sizes the mix can dispatch.
    The server's FDR step compiles once per batch size, so an open loop,
    which dispatches whatever has queued, needs every size up to the cap;
    a backlog dispatches full batches only."""
    if mix["arrival"] == "backlog":
        return [max_batch] * 8
    return list(range(1, max_batch + 1))


WARM_SWEEPS = 4


def warm_up(dep, pool, sizes: list[int], compiles) -> int:
    """Serve a batch of each size, with random precursors, until a whole
    sweep compiles nothing new; returns the sweeps run. The OMS tile
    budget of a batch depends on how far its precursors spread, so the
    same size can reach several programs."""
    row = 0
    for sweep in range(1, WARM_SWEEPS + 1):
        before = compiles()
        for n in sizes:
            for i in range(row, row + n):
                dep.submit(pool.levels[i], pool.precursor[i])
            row += n
            while dep.pending():
                if not dep.step():
                    time.sleep(IDLE_SLEEP_S)
        if compiles() == before:
            return sweep
    return WARM_SWEEPS


def run(dep, mix: dict, pool, seconds: float, seed: int, max_batch: int,
        annotate) -> Window:
    """Drive the window; returns when it closes (answers still in flight
    are collected by :func:`drain`)."""
    clock = time.monotonic
    w = Window(t0=0.0, t_end=0.0, due={}, pool_row={}, served={},
               attempted=[])
    if mix["arrival"] == "poisson":
        n = len(pool)
        offsets = np.sort(np.random.default_rng(seed).uniform(
            0.0, seconds, n))
        w.t0 = clock()
        w.t_end = w.t0 + seconds
        due = w.t0 + offsets
        i = 0
        while True:
            now = clock()
            if i < n and due[i] <= now:
                with annotate("bench.submit"):
                    while i < n and due[i] <= now:
                        rid = dep.submit(pool.levels[i], pool.precursor[i])
                        w.due[rid], w.pool_row[rid] = float(due[i]), i
                        w.attempted.append(rid)
                        i += 1
            done = dep.step()
            for s in done:
                w.served[s.rid] = s
            if now >= w.t_end and i == n:
                break
            if not done and (i == n or due[i] > now):
                wait = IDLE_SLEEP_S if i == n else due[i] - now
                time.sleep(min(wait, IDLE_SLEEP_S))
        return w

    depth = int(mix["backlog_batches"]) * max_batch
    nxt = 0
    w.t0 = clock()
    w.t_end = w.t0 + seconds
    while True:
        now = clock()
        if now >= w.t_end:
            break
        if dep.queued() < depth and nxt < len(pool):
            with annotate("bench.submit"):
                while dep.queued() < depth and nxt < len(pool):
                    rid = dep.submit(pool.levels[nxt], pool.precursor[nxt])
                    w.due[rid], w.pool_row[rid] = now, nxt
                    nxt += 1
        done = dep.step()
        for s in done:
            w.served[s.rid] = s
        if not done:
            time.sleep(IDLE_SLEEP_S)
    # the backlog that never left the queue was not asked for in the window;
    # what was dispatched is waited for, and the window closes when it is
    # all back, so every batch sent counts, over all the time it took
    dispatched = {r for b in dep.batches for r in b.rids}
    for rid in w.due:
        if rid not in dispatched:
            dep.cancel(rid)
    w.attempted = [r for r in w.due if r in dispatched]
    drain(dep, w)
    w.t_close = clock()
    return w


def drain(dep, w: Window) -> None:
    """Collect the answers still outstanding, for up to a minute."""
    limit = time.monotonic() + DRAIN_LIMIT_S
    want = set(w.attempted) - set(w.served)
    while want and time.monotonic() < limit:
        done = dep.step()
        for s in done:
            w.served[s.rid] = s
            want.discard(s.rid)
        if not done:
            time.sleep(IDLE_SLEEP_S)
