"""The one load generator: it reads a traffic mix (``bench/traffic/*.json``)
and drives the deployment from a single thread.

Arrival processes:

* ``poisson`` — an open loop at ``rate_per_s``. A run of S seconds offers
  exactly round(rate * S) requests, due at sorted uniform times over the
  window: a Poisson process conditioned on its count, so every seed offers
  the same amount of work. Each request's latency runs from when it was
  due, so a late generator or a stalled server shows in the tail. With
  ``preroll_s`` the same process runs that long before the window opens
  (round(rate * preroll_s) requests, never counted), so the window finds
  the queue and the batches at their steady state, not filling from
  empty.
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
from tracereduce import WINDOW_SPAN

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
    t_start: float = 0.0  # when the run began: the pre-roll, else t0


def preroll_size(mix: dict) -> int:
    return int(round(mix["rate_per_s"] * mix.get("preroll_s", 0.0)))


def pool_size(mix: dict, seconds: float) -> int:
    if mix["arrival"] == "poisson":
        return preroll_size(mix) + int(round(mix["rate_per_s"] * seconds))
    if mix["arrival"] == "backlog":
        return int(mix["pool"])
    raise ValueError(f"unknown arrival process {mix['arrival']!r}")


WARM_SWEEPS = 4
# shares of the pool's precursor range that an open-loop warm batch spreads
# over, in steps of sqrt(2): the OMS tile budget (a power of two) of each
# span from the window's alone up to the whole range is then reached
SPREADS = tuple(2.0 ** (-j / 2) for j in range(25)) + (0.0,)


def _stratified_backlog(mix: dict) -> bool:
    """A backlog of stratified runs: full batches that all span the
    precursor range alike, so all take one OMS tile budget."""
    return (mix["arrival"] == "backlog"
            and mix.get("precursor_order", "stratified") == "stratified")


def warm_pool_size(mix: dict, max_batch: int) -> int:
    if _stratified_backlog(mix):
        return WARM_SWEEPS * 8 * max_batch
    return max_batch


def warm_batches(mix: dict, max_batch: int, buckets, pool, lo: float,
                 hi: float) -> list[list[tuple[np.ndarray, np.ndarray]]]:
    """The (levels, precursors) batches of each warm-up sweep.

    A backlog of stratified runs dispatches full batches at one budget
    only: eight a sweep, of fresh pool spectra. Any other mix reaches more
    programs: the server compiles one per (bucket, OMS tile budget), an
    open loop dispatches any size, and the budget follows the widest
    precursor span of a block of the sorted batch, which random precursors
    vary from batch to batch. So one sweep sends a batch of each bucket's
    size at each of ``SPREADS``: one request at ``lo`` (the lowest
    precursor the window sends, whose search window the library's edge
    cuts shortest) and the rest at ``lo + share * (hi - lo)``; every sweep
    is the same."""
    if _stratified_backlog(mix):
        n = max_batch
        return [[(pool.levels[i:i + n], pool.precursor[i:i + n])
                 for i in range(8 * n * s, 8 * n * (s + 1), n)]
                for s in range(WARM_SWEEPS)]
    sweep = []
    for b in buckets:
        for share in SPREADS:
            prec = np.full(b, lo + share * (hi - lo), np.float32)
            prec[0] = lo
            sweep.append((pool.levels[:b], prec))
    return [sweep] * WARM_SWEEPS


def warm_up(dep, sweeps, compiles) -> int:
    """Serve each sweep's batches one at a time until a whole sweep
    compiles nothing new; returns the sweeps run."""
    for i, batches in enumerate(sweeps, 1):
        before = compiles()
        for levels, prec in batches:
            for lv, p in zip(levels, prec):
                dep.submit(lv, p)
            while dep.pending():
                if not dep.step():
                    time.sleep(IDLE_SLEEP_S)
        if compiles() == before:
            return i
    return len(sweeps)


def run(dep, mix: dict, pool, seconds: float, seed: int, max_batch: int,
        annotate, on_open=lambda: None) -> Window:
    """Drive the window; returns when it closes (answers still in flight
    are collected by :func:`drain`). ``on_open`` is called as the window
    opens, after any pre-roll; the window runs inside the harness span
    the trace's window is read from."""
    clock = time.monotonic
    w = Window(t0=0.0, t_end=0.0, due={}, pool_row={}, served={},
               attempted=[])
    if mix["arrival"] == "poisson":
        _open_loop(dep, mix, pool, seconds, seed, annotate, on_open, w)
        return w

    depth = int(mix["backlog_batches"]) * max_batch
    nxt = 0
    on_open()
    with annotate(WINDOW_SPAN):
        w.t_start = w.t0 = clock()
        w.t_end = w.t0 + seconds
        while True:
            now = clock()
            if now >= w.t_end:
                break
            if dep.queued() < depth and nxt < len(pool):
                with annotate("bench.submit"):
                    while dep.queued() < depth and nxt < len(pool):
                        rid = dep.submit(pool.levels[nxt],
                                         pool.precursor[nxt])
                        w.due[rid], w.pool_row[rid] = now, nxt
                        nxt += 1
            done = dep.step()
            for s in done:
                w.served[s.rid] = s
            if not done:
                time.sleep(IDLE_SLEEP_S)
        # the backlog that never left the queue was not asked for in the
        # window; what was dispatched is waited for, and the window closes
        # when it is all back, so every batch sent counts, over all the
        # time it took
        dispatched = {r for b in dep.batches for r in b.rids}
        for rid in w.due:
            if rid not in dispatched:
                dep.cancel(rid)
        w.attempted = [r for r in w.due if r in dispatched]
        drain(dep, w)
        w.t_close = clock()
    return w


def _open_loop(dep, mix, pool, seconds, seed, annotate, on_open,
               w: Window) -> None:
    """Pre-roll, then the window: requests due at their times, the server
    stepped in between. Only the window's requests are ``attempted``."""
    clock = time.monotonic
    pre, n = preroll_size(mix), len(pool)
    rng = np.random.default_rng(seed)
    offsets = np.concatenate([
        np.sort(rng.uniform(-mix.get("preroll_s", 0.0), 0.0, pre)),
        np.sort(rng.uniform(0.0, seconds, n - pre))])
    w.t_start = clock()
    w.t0 = w.t_start + mix.get("preroll_s", 0.0)
    w.t_end = w.t0 + seconds
    due = w.t0 + offsets
    i = 0

    def serve(until: float, stop: int) -> None:
        nonlocal i
        while True:
            now = clock()
            if i < stop and due[i] <= now:
                with annotate("bench.submit"):
                    while i < stop and due[i] <= now:
                        rid = dep.submit(pool.levels[i], pool.precursor[i])
                        w.due[rid], w.pool_row[rid] = float(due[i]), i
                        if i >= pre:
                            w.attempted.append(rid)
                        i += 1
            done = dep.step()
            for s in done:
                w.served[s.rid] = s
            if now >= until and i == stop:
                return
            if not done and (i == stop or due[i] > now):
                wait = IDLE_SLEEP_S if i == stop else due[i] - now
                time.sleep(min(wait, IDLE_SLEEP_S))

    serve(w.t0, pre)
    on_open()
    with annotate(WINDOW_SPAN):
        serve(w.t_end, n)


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
