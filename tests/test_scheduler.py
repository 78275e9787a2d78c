"""Continuous-batching scheduler: deterministic tests over the seams.

Everything here runs against the two injectable seams the scheduler was
built around — a settable fake clock and fake executors (recording /
simulated-service-time) — so admission order, tenant fairness, slot
accounting, cancellation, and the tail-latency behavior of both queue
modes are asserted exactly, with no real time and no device.
"""

import numpy as np
import pytest

from repro.serve import (
    ContinuousScheduler,
    DBSearchServer,
    MicroBatchQueue,
    shard_database,
)


class Clock:
    """Settable fake clock (the queue/scheduler/server time seam)."""

    def __init__(self, now: float = 0.0):
        self.now = now

    def __call__(self) -> float:
        return self.now


class RecordingExecutor:
    """Executor seam fake: records every dispatched batch; completion is
    test-controlled via ``ready`` handles."""

    def __init__(self, clock):
        self.clock = clock
        self.dispatched = []          # list[list[Request]] in dispatch order
        self.ready = set()            # handles poll() reports complete
        self._handles = {}
        self._next = 0

    def dispatch(self, reqs):
        t = self.clock()
        for r in reqs:
            r.t_dispatch = t
        h = self._next
        self._next += 1
        self.dispatched.append(list(reqs))
        self._handles[h] = reqs
        return h

    def poll(self, h):
        return h in self.ready

    def finalize(self, h):
        reqs = self._handles.pop(h)
        t = self.clock()
        live = [r for r in reqs if not r.cancelled]
        for r in live:
            r.t_done = t
            r.result = "done"
        return live


class SimulatedExecutor:
    """Executor seam fake with a serial device model: each dispatch takes
    ``c0 + c1 * batch`` seconds of device time, batches execute one after
    another (a single accelerator), and ``finalize`` advances the fake
    clock to the completion time when asked to block early."""

    def __init__(self, clock, c0=0.01, c1=0.0025):
        self.clock = clock
        self.c0, self.c1 = c0, c1
        self._free_at = 0.0
        self._handles = {}
        self._next = 0

    def dispatch(self, reqs):
        t = self.clock()
        for r in reqs:
            r.t_dispatch = t
        start = max(t, self._free_at)
        t_ready = start + self.c0 + self.c1 * len(reqs)
        self._free_at = t_ready
        h = self._next
        self._next += 1
        self._handles[h] = (reqs, t_ready)
        return h

    def poll(self, h):
        return self.clock() >= self._handles[h][1]

    def finalize(self, h):
        reqs, t_ready = self._handles.pop(h)
        self.clock.now = max(self.clock.now, t_ready)  # block on the device
        live = [r for r in reqs if not r.cancelled]
        for r in live:
            r.t_done = self.clock()
            r.result = "done"
        return live


def _make(clock, *, max_batch=2, num_slots=2, fairness_cap=None,
          flush_timeout_s=0.5):
    queue = MicroBatchQueue(max_batch_size=max_batch,
                            flush_timeout_s=flush_timeout_s, clock=clock,
                            fairness_cap=fairness_cap)
    ex = RecordingExecutor(clock)
    sched = ContinuousScheduler(queue, ex, num_slots=num_slots)
    return queue, ex, sched


# --------------------------------------------------------------------------
# admission, slot accounting, refill
# --------------------------------------------------------------------------

class TestAdmission:
    def test_fifo_admission_fills_slots_in_order(self):
        clock = Clock()
        queue, ex, sched = _make(clock)
        rids = [queue.submit(i) for i in range(6)]
        assert sched.admit() == 2           # both slots filled, no waiting
        assert sched.in_flight == 2 and sched.free_slots == 0
        assert [[r.rid for r in b] for b in ex.dispatched] == [
            rids[0:2], rids[2:4]]
        assert len(queue) == 2              # backlog held until a slot frees
        assert sched.admit() == 0           # no free slot -> no admission

    def test_retire_then_admit_refills_freed_slot_same_step(self):
        clock = Clock()
        queue, ex, sched = _make(clock)
        rids = [queue.submit(i) for i in range(6)]
        sched.admit()
        ex.ready.add(0)
        clock.now = 1.0
        done = sched.step()
        assert [r.rid for r in done] == rids[0:2]
        assert sched.in_flight == 2         # freed slot refilled this step
        assert [r.rid for r in ex.dispatched[2]] == rids[4:6]
        assert sched.retired_batches == 1 and sched.dispatched_batches == 3

    def test_admission_needs_no_flush_trigger(self):
        """The continuous mode's defining property: a lone request is
        admitted immediately — no full lane, no flush timeout."""
        clock = Clock()
        queue, ex, sched = _make(clock, max_batch=8, flush_timeout_s=10.0)
        rid = queue.submit(0)
        assert not queue.ready()            # flush-sync would sit on this
        assert sched.step() == []           # nothing finished yet...
        assert sched.in_flight == 1         # ...but the request is in flight
        assert ex.dispatched[0][0].rid == rid
        assert ex.dispatched[0][0].queue_wait_s == 0.0

    def test_step_block_waits_out_in_flight_slots(self):
        clock = Clock()
        queue, ex, sched = _make(clock)
        queue.submit(0)
        sched.step()
        done = sched.step(block=True)       # finalize without poll-ready
        assert len(done) == 1 and sched.in_flight == 0

    def test_drain_empties_queue_and_slots(self):
        clock = Clock()
        queue, ex, sched = _make(clock, max_batch=3, num_slots=2)
        rids = [queue.submit(i) for i in range(10)]
        done = sched.drain()
        assert sorted(r.rid for r in done) == rids
        assert sched.in_flight == 0 and len(queue) == 0
        assert sched.dispatched_batches == sched.retired_batches == 4

    def test_num_slots_validation(self):
        clock = Clock()
        queue, ex, _ = _make(clock)
        with pytest.raises(ValueError, match="num_slots"):
            ContinuousScheduler(queue, ex, num_slots=0)


# --------------------------------------------------------------------------
# tenant fairness and starvation
# --------------------------------------------------------------------------

class TestFairness:
    def test_fairness_cap_under_skewed_load(self):
        """One hot tenant floods; the cap bounds its per-batch take while
        the cold tenant waits, and the rotation serves the cold tenant on
        the very next admission."""
        clock = Clock()
        queue, ex, sched = _make(clock, max_batch=4, num_slots=8,
                                 fairness_cap=2)
        for i in range(8):
            queue.submit(i, tenant="hot")
        queue.submit(99, tenant="cold")
        sched.admit()
        batches = [(b[0].tenant, len(b)) for b in ex.dispatched]
        # capped at 2 while cold waits, cold next, then hot uncapped
        assert batches == [("hot", 2), ("cold", 1), ("hot", 4), ("hot", 2)]

    def test_cold_tenant_not_starved_with_one_slot(self):
        """Even with a single slot and a hot tenant that keeps its lane
        full, the skip-last-served rotation admits the cold tenant on the
        second admission — its wait is one batch, not unbounded."""
        clock = Clock()
        queue, ex, sched = _make(clock, max_batch=4, num_slots=1,
                                 fairness_cap=4)
        for i in range(4):
            queue.submit(i, tenant="hot")
        cold_rid = queue.submit(99, tenant="cold")
        sched.step()
        for i in range(4):                   # hot keeps flooding
            queue.submit(10 + i, tenant="hot")
        ex.ready.add(0)
        sched.step()
        assert ex.dispatched[1][0].rid == cold_rid
        assert [b[0].tenant for b in ex.dispatched] == ["hot", "cold"]


# --------------------------------------------------------------------------
# cancellation and slot accounting
# --------------------------------------------------------------------------

class TestCancellation:
    def test_pending_cancel_removes_from_queue(self):
        clock = Clock()
        queue, ex, sched = _make(clock, max_batch=2, num_slots=1)
        rids = [queue.submit(i) for i in range(4)]
        sched.admit()                        # rids[0:2] in flight
        assert sched.cancel(rids[2]) is True
        assert len(queue) == 1               # removed before dispatch
        ex.ready.add(0)
        done = sched.drain()
        assert sorted(r.rid for r in done) == [rids[0], rids[1], rids[3]]
        assert sched.cancellations == 1

    def test_in_flight_cancel_keeps_slot_accounting(self):
        """Cancelling an in-flight request marks it (device work is not
        restartable) without perturbing slots: the batch retires as one
        unit and only the cancelled result is dropped."""
        clock = Clock()
        queue, ex, sched = _make(clock, max_batch=2, num_slots=2)
        rids = [queue.submit(i) for i in range(4)]
        sched.admit()
        assert sched.cancel(rids[1]) is True
        assert sched.in_flight == 2          # slot untouched
        assert sched.in_flight_requests() == 4
        ex.ready.update({0, 1})
        done = sched.step()
        assert [r.rid for r in done] == [rids[0], rids[2], rids[3]]
        assert sched.retired_batches == 2    # both slots retired whole
        assert sched.cancel(rids[0]) is False  # already finished

    def test_unknown_rid_cancel_returns_false(self):
        clock = Clock()
        _, _, sched = _make(clock)
        assert sched.cancel(123) is False
        assert sched.cancellations == 0


# --------------------------------------------------------------------------
# latency accounting: t_submit at enqueue, t_dispatch at queue exit
# --------------------------------------------------------------------------

class TestLatencyAccounting:
    def test_queue_wait_visible_in_continuous_mode(self):
        clock = Clock()
        queue = MicroBatchQueue(max_batch_size=4, clock=clock)
        ex = SimulatedExecutor(clock, c0=0.1, c1=0.0)
        sched = ContinuousScheduler(queue, ex, num_slots=1)
        queue.submit(0)
        clock.now = 0.3                      # sat in the queue 0.3s
        done = sched.drain()
        (r,) = done
        assert r.queue_wait_s == pytest.approx(0.3)
        assert r.service_s == pytest.approx(0.1)
        assert r.latency_s == pytest.approx(0.4)  # includes the queue wait

    def test_queue_wait_visible_in_flush_sync_mode(self):
        """Regression pin for the starts-at-flush latency bug class:
        ``t_submit`` is stamped at enqueue, so a request that waits out
        the flush timeout shows that wait in ``latency_s`` — and the
        ``t_dispatch`` split exposes it as queue wait, not service."""
        clock = Clock()
        db = _tiny_db(7)
        server = DBSearchServer(db, k=2, fdr=0.5, max_batch_size=4,
                                flush_timeout_s=1.0, clock=clock)
        server.submit(_tiny_query(7))
        assert server.step() == []           # not flushable yet
        clock.now = 1.5
        (r,) = server.step()
        assert r.t_submit == 0.0             # stamped at enqueue, not flush
        assert r.queue_wait_s == pytest.approx(1.5)
        assert r.latency_s == pytest.approx(1.5)
        s = server.summary()
        assert s["queue_wait_p50_ms"] == pytest.approx(1500.0)

    def test_stats_summary_reports_queue_wait_percentiles(self):
        clock = Clock()
        queue = MicroBatchQueue(max_batch_size=2, clock=clock)
        ex = SimulatedExecutor(clock, c0=0.05, c1=0.0)
        sched = ContinuousScheduler(queue, ex, num_slots=1)
        from repro.serve import LatencyStats
        stats = LatencyStats()
        for _ in range(4):
            queue.submit(0)
        clock.now = 0.2
        stats.record_batch(sched.drain())
        s = stats.summary()
        assert s["queue_wait_p50_ms"] > 0.0
        assert s["queue_wait_p95_ms"] >= s["queue_wait_p50_ms"]
        assert s["p50_ms"] > s["queue_wait_p50_ms"]  # service on top


# --------------------------------------------------------------------------
# tail latency: continuous vs flush-sync on an open-loop trace
# --------------------------------------------------------------------------

def _drive(trace, clock, queue, step_fn, drain_fn, tick=0.005):
    """Open-loop driver: arrivals happen at their trace times regardless
    of server progress; between arrivals the serving loop ticks."""
    done = []
    for t_arrival, n in trace:
        while clock.now < t_arrival:
            clock.now = min(t_arrival, clock.now + tick)
            done.extend(step_fn())
        for _ in range(n):
            queue.submit(0)
        done.extend(step_fn())
    done.extend(drain_fn())
    return done


def _open_loop_trace():
    """Steady full bursts (the happy path) plus ~9% lone stragglers, each
    followed by a gap longer than the flush timeout — the traffic shape
    that makes flush-and-wait's p95 collapse."""
    trace = []
    t = 0.0
    for _ in range(10):
        trace.append((t, 8))
        t += 0.08
    for _ in range(8):
        trace.append((t, 1))
        t += 0.7
    return trace


class TestTailLatency:
    FLUSH_TIMEOUT = 0.5

    def _run_flush_sync(self, trace):
        clock = Clock()
        queue = MicroBatchQueue(max_batch_size=8,
                                flush_timeout_s=self.FLUSH_TIMEOUT,
                                clock=clock)
        ex = SimulatedExecutor(clock)

        def step():
            if not queue.ready():
                return []
            return ex.finalize(ex.dispatch(queue.take_batch()))

        def drain():
            done = []
            while len(queue):
                done.extend(ex.finalize(ex.dispatch(queue.take_batch())))
            return done

        return _drive(trace, clock, queue, step, drain)

    def _run_continuous(self, trace):
        clock = Clock()
        queue = MicroBatchQueue(max_batch_size=8,
                                flush_timeout_s=self.FLUSH_TIMEOUT,
                                clock=clock)
        sched = ContinuousScheduler(queue, SimulatedExecutor(clock),
                                    num_slots=2)
        return _drive(trace, clock, queue, sched.step, sched.drain)

    def test_continuous_holds_p95_within_4x_p50(self):
        trace = _open_loop_trace()
        total = sum(n for _, n in trace)

        sync_done = self._run_flush_sync(trace)
        cont_done = self._run_continuous(trace)
        assert len(sync_done) == len(cont_done) == total

        def ratio(done):
            lat = np.asarray([r.latency_s for r in done])
            return float(np.percentile(lat, 95) / np.percentile(lat, 50))

        sync_ratio, cont_ratio = ratio(sync_done), ratio(cont_done)
        # flush-and-wait strands every straggler on the flush timeout;
        # continuous admits it on the next tick
        assert sync_ratio > 4.0, sync_ratio
        assert cont_ratio <= 4.0, cont_ratio
        # and the improvement is structural, not marginal
        assert cont_ratio < sync_ratio / 2


# --------------------------------------------------------------------------
# both modes through the real executor: bit-identical results, bucket reuse
# --------------------------------------------------------------------------

def _tiny_db(seed, n=24, d=64):
    rng = np.random.default_rng(seed)
    import jax.numpy as jnp
    refs = jnp.asarray(rng.choice([-1, 1], size=(n, d)).astype(np.int8))
    decoys = jnp.asarray(rng.choice([-1, 1], size=(n, d)).astype(np.int8))
    return shard_database(refs, decoys=decoys)


def _tiny_query(seed, d=64):
    rng = np.random.default_rng(seed)
    return rng.choice([-1, 1], size=d).astype(np.int8)


class TestServerModes:
    def test_continuous_and_flush_sync_bit_identical(self):
        """Both queue modes run the identical SearchExecutor device path,
        so per-request results must match exactly."""
        queries = [_tiny_query(100 + i) for i in range(7)]
        results = {}
        for continuous in (False, True):
            clock = Clock()
            server = DBSearchServer(_tiny_db(3), k=3, fdr=0.5,
                                    max_batch_size=4, flush_timeout_s=0.01,
                                    clock=clock, continuous=continuous,
                                    num_slots=2)
            rids = [server.submit(q) for q in queries]
            done = server.run_until_drained()
            assert sorted(r.rid for r in done) == rids
            results[continuous] = {
                r.rid: (tuple(r.result.indices), tuple(r.result.scores),
                        r.result.match) for r in done}
            assert server.summary()["mode"] == (
                "continuous" if continuous else "flush-sync")
        assert results[False] == results[True]

    def test_bucket_reuse_across_admissions(self):
        """Equal-size admissions pad to the same shape bucket, so the jit
        signature is reused instead of recompiling per ragged batch."""
        clock = Clock()
        server = DBSearchServer(_tiny_db(4), k=2, fdr=0.5, max_batch_size=8,
                                clock=clock, buckets=2, continuous=True,
                                num_slots=1)
        for i in range(3):
            server.submit(_tiny_query(i))
        server.run_until_drained()
        for i in range(3):
            server.submit(_tiny_query(10 + i))
        server.run_until_drained()
        buckets = server.summary()["buckets"]
        assert buckets == {4: 2}             # same bucket both rounds

    def test_server_cancel_roundtrip(self):
        clock = Clock()
        server = DBSearchServer(_tiny_db(5), k=2, fdr=0.5, max_batch_size=8,
                                clock=clock, continuous=True, num_slots=1)
        rids = [server.submit(_tiny_query(i)) for i in range(3)]
        assert server.cancel(rids[1]) is True
        done = server.run_until_drained()
        assert sorted(r.rid for r in done) == [rids[0], rids[2]]
