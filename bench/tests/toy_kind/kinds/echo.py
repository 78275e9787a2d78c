"""A toy deployment kind, for the harness's tests: every spectrum is
answered with its own precursor, a batch at a time, through one jitted
program on the device. Its reference checks that each sampled answer is
the precursor that was sent; its control answers with the precursor
rounded to a whole dalton."""

from __future__ import annotations

import dataclasses
import time

import numpy as np
from tracereduce import Annotate


@dataclasses.dataclass
class Pool:
    levels: np.ndarray      # (Q, F) uint8
    precursor: np.ndarray   # (Q,) float32

    def __len__(self) -> int:
        return int(self.precursor.shape[0])


@dataclasses.dataclass
class Batch:
    rids: list[int]
    t_dispatch: float
    dispatch_s: float
    finalize_s: float = 0.0


@dataclasses.dataclass
class Served:
    rid: int
    t_submit: float
    t_dispatch: float
    t_done: float
    precursor: float


class Echo:
    def __init__(self, max_batch: int):
        import jax
        self.buckets = (max_batch,)
        self.batches: list[Batch] = []
        self.annotate = Annotate(False)
        self._queue: dict[int, tuple[float, float]] = {}  # submit order
        self._next = 0
        self._echo = jax.jit(lambda p: p + 0.0)

    def submit(self, levels, precursor) -> int:
        rid, self._next = self._next, self._next + 1
        self._queue[rid] = (time.monotonic(), float(precursor))
        return rid

    def cancel(self, rid: int) -> bool:
        return self._queue.pop(rid, None) is not None

    def pending(self) -> int:
        return len(self._queue)

    def queued(self) -> int:
        return len(self._queue)

    def step(self) -> list[Served]:
        if not self._queue:
            return []
        t0 = time.monotonic()
        rids = list(self._queue)[:self.buckets[0]]
        sent = [self._queue.pop(r) for r in rids]
        with self.annotate("bench.dispatch"):
            prec = np.zeros(self.buckets[0], np.float32)
            prec[:len(rids)] = [p for _, p in sent]
            out = np.asarray(self._echo(prec))
        t1 = time.monotonic()
        self.batches.append(Batch(rids, t0, t1 - t0))
        return [Served(r, t_sub, t0, t1, float(out[i]))
                for i, (r, (t_sub, _)) in enumerate(zip(rids, sent))]

    def close(self) -> None:
        self._echo = None


def _pool(seed: int, count: int, num_bins: int) -> Pool:
    rng = np.random.default_rng(seed % 2**64)
    return Pool(levels=rng.integers(0, 16, (count, num_bins), np.uint8),
                precursor=rng.uniform(400, 1600, count).astype(np.float32))


def bring_up(cfg, mix, seed, pools, compiles, log):
    import loadgen

    dep = Echo(cfg["serving"]["max_batch_size"])
    made = [_pool(s, count, cfg["num_bins"]) for s, count in pools]
    warm = _pool(seed, dep.buckets[0], cfg["num_bins"])
    sweeps = loadgen.warm_up(dep, [[(warm.levels, warm.precursor)]] * 2,
                             lambda: compiles[0])
    log(f"set-up echo: {sweeps} warm-up sweeps")
    return dep, made


def counters(dep) -> dict:
    return {"batches": len(dep.batches)}


def describe(rec, dep, pool, w, log) -> None:
    log(f"echo: {len(rec.window_batches)} window batches")


def checks(rec, dep, pool, w, batches, control, log):
    rids = [r for b in batches for r in b]
    sent = {r: float(pool.precursor[w.pool_row[r]]) for r in rids}
    got = ({r: float(round(p)) for r, p in sent.items()} if control
           else {r: w.served[r].precursor for r in rids})
    bad = sum(got[r] != sent[r] for r in rids)
    return {"precursor_mismatch": {"value": bad, "limit": 0}}, len(rids)


def end_to_end(name, rec):
    """``answered_per_batch``: this kind's own metric, the mean requests
    of a window batch."""
    if name != "answered_per_batch":
        raise ValueError(f"no end-to-end metric {name!r} in the echo kind")
    b = rec.window_batches
    return sum(len(x.rids) for x in b) / len(b)
