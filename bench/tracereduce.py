"""From a profiler trace to device busy time, idle gaps and op totals.

The trace is JAX's ``.xplane.pb``. Device operations are the events of the
``XLA Ops`` line of each ``/device:<chip>:<i>`` plane; the harness's own
host spans are the ``bench.*`` events of the host plane, on the same
clock. Busy time is the union of the device-op intervals inside the
traced window, averaged over the chips; an idle gap is a stretch of the
window with no device op, labelled by the host span it overlaps most.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os

WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."
DEVICE_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float  # ns
    end: float    # ns


@dataclasses.dataclass
class Trace:
    device_ops: dict[str, list[Span]]  # plane name -> ops
    host_spans: list[Span]


def load(log_dir: str) -> Trace:
    """Read the one ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file under {log_dir}, "
                           f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = [Span(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for line in plane.lines if line.name == DEVICE_LINE
                   for e in line.events]
            if ops:
                device[plane.name] = ops
        elif plane.name.startswith("/host:"):
            host.extend(Span(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for line in plane.lines for e in line.events
                        if e.name.startswith(HOST_PREFIX))
    return Trace(device_ops=device, host_spans=host)


def merged(spans, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of the spans' intervals, clipped to [lo, hi], in order."""
    out: list[list[float]] = []
    for s in sorted(spans, key=lambda s: s.start):
        a, b = max(s.start, lo), min(s.end, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float
         ) -> list[tuple[float, float]]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap: tuple[float, float], host: list[Span]) -> str:
    """The host span that overlaps the gap most, else 'no harness span'."""
    best, name = 0.0, "no harness span"
    for s in host:
        ov = min(gap[1], s.end) - max(gap[0], s.start)
        if ov > best and s.name != WINDOW_SPAN:
            best, name = ov, s.name
    return name


def reduce(trace: Trace, top: int = 10) -> dict | None:
    """busy_s (mean over chips), window_s, the top device ops by total
    time and the longest idle gaps, each as [name, seconds]. None when the
    trace holds no window span or no device op."""
    win = [s for s in trace.host_spans if s.name == WINDOW_SPAN]
    if not win or not trace.device_ops:
        return None
    lo, hi = win[0].start, win[0].end
    busy_ns, idle = [], []
    totals: collections.Counter[str] = collections.Counter()
    for ops in trace.device_ops.values():
        busy = merged(ops, lo, hi)
        busy_ns.append(sum(b - a for a, b in busy))
        idle.extend(gaps(busy, lo, hi))
        for s in ops:
            d = min(s.end, hi) - max(s.start, lo)
            if d > 0:
                totals[s.name] += d
    idle.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[n, t / 1e9 / len(busy_ns)]
                       for n, t in totals.most_common(top)],
        "idle_gaps": [[label(g, trace.host_spans), (g[1] - g[0]) / 1e9]
                      for g in idle[:top]],
    }
