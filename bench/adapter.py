"""The one place where the benchmark spells out the program's API.

The harness builds the served bank and the server here, submits and
steps through here, and reads the program's counters here. It passes the
deployment's settings and nothing else: the fused bank, continuous
batching, raw spectra in through the fused encode->search route, the
open-modification window, k, FDR and the batch cap. Buckets and slots
stay at the server's defaults, so a change to those defaults is measured.

The executor's two host calls are wrapped in harness spans (host clock,
and a profiler annotation when a trace is on); the batches they carry are
recorded for the reference and the work count.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np
from tracereduce import Annotate

from repro.serve import DBSearchServer, OMSConfig, QueryEncoder, shard_database


@dataclasses.dataclass
class Batch:
    rids: list[int]          # request ids in dispatch order
    t_dispatch: float        # host clock when dispatch began
    dispatch_s: float
    finalize_s: float = 0.0


@dataclasses.dataclass
class Served:
    """One finished request as the harness keeps it."""

    rid: int
    t_submit: float
    t_dispatch: float
    t_done: float
    indices: np.ndarray
    scores: np.ndarray
    accept: bool
    match: int
    has_candidate: bool


class Deployment:
    def __init__(self, lib, serving: dict, window: dict, *, fused_e2e=True):
        self.lib = lib  # on the host, for the reference after close()
        self.db = shard_database(lib.targets, decoys=lib.decoys, fused=True,
                                 precursor=lib.precursor)
        jax.block_until_ready(self.db.data)
        enc = QueryEncoder(id_hvs=jax.numpy.asarray(lib.id_hvs),
                           level_hvs=jax.numpy.asarray(lib.level_hvs))
        self.server = DBSearchServer(
            self.db, k=serving["k"], fdr=serving["fdr"],
            max_batch_size=serving["max_batch_size"], continuous=True,
            oms=OMSConfig(tol=window["tol"], open_tol=window["open_tol"]),
            encoder=enc, fused_e2e=fused_e2e)
        self.batches: list[Batch] = []
        self.annotate = Annotate(False)
        ex = self.server.executor
        dispatch, finalize = ex.dispatch, ex.finalize
        by_first: dict[int, Batch] = {}

        def timed_dispatch(reqs):
            t0 = time.monotonic()
            with self.annotate("bench.dispatch"):
                handle = dispatch(reqs)
            b = Batch(rids=[r.rid for r in reqs], t_dispatch=t0,
                      dispatch_s=time.monotonic() - t0)
            self.batches.append(b)
            by_first[reqs[0].rid] = b
            return handle

        def timed_finalize(handle):
            t0 = time.monotonic()
            with self.annotate("bench.finalize"):
                done = finalize(handle)
            by_first[handle.reqs[0].rid].finalize_s = time.monotonic() - t0
            return done

        ex.dispatch, ex.finalize = timed_dispatch, timed_finalize

    def submit(self, levels: np.ndarray, precursor: float) -> int:
        return self.server.submit(levels, precursor=float(precursor))

    def cancel(self, rid: int) -> bool:
        return self.server.cancel(rid)

    def step(self) -> list[Served]:
        return [self._served(r) for r in self.server.step()]

    def pending(self) -> int:
        """Requests queued or in flight."""
        s = self.server
        return len(s.queue) + s.scheduler.in_flight_requests()

    def queued(self) -> int:
        return len(self.server.queue)

    @property
    def buckets(self) -> tuple[int, ...]:
        """The batch sizes the server pads a batch up to."""
        return self.server.buckets

    def oms_counters(self) -> dict:
        """Sums over all batches so far of the planner's candidate and
        scanned fractions (the server reports their means)."""
        o = self.server.summary()["oms"]
        return {"batches": o["batches"],
                "candidate_sum": o["candidate_fraction"] * o["batches"],
                "scanned_sum": o["scanned_fraction"] * o["batches"]}

    def close(self) -> None:
        """Drop the bank and the server. They point at each other, so the
        caller collects cycles before the device memory is free."""
        self.server = self.db = None

    @staticmethod
    def _served(r) -> Served:
        res = r.result
        return Served(rid=r.rid, t_submit=r.t_submit, t_dispatch=r.t_dispatch,
                      t_done=r.t_done, indices=np.asarray(res.indices),
                      scores=np.asarray(res.scores), accept=bool(res.accept),
                      match=int(res.match),
                      has_candidate=bool(res.has_candidate))
