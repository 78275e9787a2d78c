"""What the per-layer metrics read from a run's record. Each metric in
``layer_metrics/`` is a file of its own that names one of these; a reader
that finds nothing to read returns None, and the metric is left out."""

from __future__ import annotations

import math

import work


def percentile(values, p: float) -> float | None:
    """Nearest-rank percentile (None for no values)."""
    v = sorted(values)
    if not v:
        return None
    return float(v[max(0, math.ceil(p / 100 * len(v)) - 1)])


def executor_ms(rec):
    """Host time of the executor's dispatch and finalize, mean per batch."""
    b = rec.window_batches
    if not b:
        return None
    return sum(x.dispatch_s + x.finalize_s for x in b) / len(b) * 1e3


def oms_overscan(rec):
    """Rows the planner had the kernel scan per candidate row."""
    c0, c1 = rec.counters
    cand = c1["candidate_sum"] - c0["candidate_sum"]
    if cand <= 0:
        return None
    return (c1["scanned_sum"] - c0["scanned_sum"]) / cand


def search_roofline(rec):
    """Least time of the window's batches at the chip's peaks, over the
    device's busy time in the trace (%)."""
    if not rec.trace or rec.trace["busy_s"] <= 0 or not rec.window_batches:
        return None
    least, bound = 0.0, {"ops": 0.0, "bytes": 0.0}
    for b in rec.window_batches:
        ops, nbytes = work.batch_work(
            rec.sorted_prec, [rec.precursor_of[r] for r in b.rids],
            dim=rec.config["dim"], num_bins=rec.config["num_bins"],
            tol=rec.window["tol"], open_tol=rec.window["open_tol"])
        t, which = work.least_time(ops, nbytes, rec.peaks)
        least += t
        bound[which] += t
    rec.notes.append(f"search_roofline: least time {least:.6f} s over "
                     f"{len(rec.window_batches)} batches ({bound['bytes']:.6f}"
                     f" s bound by bytes, {bound['ops']:.6f} s by ops), "
                     f"device busy {rec.trace['busy_s']:.6f} s")
    return 100.0 * least / rec.trace["busy_s"]


def device_idle(rec):
    """Share of the traced window with no operation on the device (%)."""
    if not rec.trace or rec.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - rec.trace["busy_s"] / rec.trace["window_s"])


def queue_wait_p95_ms(rec):
    """95th percentile over the window's answered requests of the wait
    from when each was due until its batch was dispatched (ms); requests
    never answered are counted by the run's ``failed``."""
    return percentile([(rec.served[r].t_dispatch - rec.due[r]) * 1e3
                       for r in rec.attempted if r in rec.served], 95)


def batch_fill(rec):
    """Mean real requests per window batch over the batch cap."""
    b = rec.window_batches
    if not b:
        return None
    cap = rec.config["serving"]["max_batch_size"]
    return sum(len(x.rids) for x in b) / (len(b) * cap)
