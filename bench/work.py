"""The necessary work of a search batch, counted from the problem's shapes.

Never from the implementation's tiles, scanned rows, padding, levels or
word chunks: the count is the same whichever kernel serves the batch, so a
roofline share read against it can only rise when the work is done
faster.
"""

from __future__ import annotations

import numpy as np


def window_ranges(sorted_prec: np.ndarray, query_prec: np.ndarray,
                  tol: float, open_tol: float) -> tuple[np.ndarray, np.ndarray]:
    """The open-modification window: a library row is a candidate for a
    query when ``query - ref`` lies strictly inside ``(-tol, open_tol)``,
    i.e. ``query - open_tol < ref < query + tol``, in float32. Returns
    per-query ``[start, end)`` positions in ``sorted_prec`` (ascending)."""
    q = np.asarray(query_prec, np.float32)
    lo = q - np.float32(open_tol)
    hi = q + np.float32(tol)
    return (np.searchsorted(sorted_prec, lo, side="right"),
            np.searchsorted(sorted_prec, hi, side="left"))


def union_length(starts: np.ndarray, ends: np.ndarray) -> int:
    """Number of positions covered by the union of ``[start, end)``."""
    total, reach = 0, None
    for s, e in sorted(zip(starts.tolist(), ends.tolist())):
        if e <= s:
            continue
        if reach is None or s >= reach:
            total += e - s
            reach = e
        elif e > reach:
            total += e - reach
            reach = e
    return total


def batch_work(sorted_prec: np.ndarray, query_prec: np.ndarray, *, dim: int,
               num_bins: int, tol: float, open_tol: float,
               blocks: int = 2) -> tuple[int, int]:
    """(operations, bytes) one batch of queries needs at the least.

    Operations: a ±1 dot of D terms with every candidate row (2·D per
    candidate) plus one bind-and-bundle per feature per dimension for each
    query (2·F·D). Bytes: every candidate row read once (D/8 bytes each,
    the union over the batch, since a row shared by two windows need be
    read only once), plus the query levels at one byte per feature.
    ``blocks`` counts the bank's blocks over one precursor list: the decoy
    block and the target block.
    """
    s, e = window_ranges(sorted_prec, query_prec, tol, open_tol)
    n = int(np.asarray(query_prec).shape[0])
    cand = blocks * int(np.sum(e - s))
    rows = blocks * union_length(s, e)
    ops = 2 * dim * cand + 2 * num_bins * dim * n
    nbytes = dim // 8 * rows + num_bins * n
    return ops, nbytes


def least_time(ops: int, nbytes: int, peaks: dict) -> tuple[float, str]:
    """Seconds at the chip's peak, and which bound governs. ±1 dots are
    priced at the int8 rate, the fastest this chip computes them."""
    t_ops = ops / peaks["int8_ops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")
