"""Open-modification search (OMS) support for the serving stack.

OMS widens the precursor window on the query side — a modified peptide is
*heavier* than its unmodified reference, so a query at mass ``m`` must be
compared against references with ``query - ref`` in ``(-tol, open_tol)``
(RapidOMS / HyperOMS convention, mirrored in
:func:`repro.spectra.preprocess.candidate_window_mask`). Candidate sets grow
~100x versus exact search, so the serving path cannot afford a full-bank
scan per query. Instead:

  * at :func:`repro.serve.db_search.shard_database` time each bank *block*
    (the decoy block, then the target block — the decoys-first order is what
    makes score ties resolve conservatively) is sorted by precursor mass,
    and the permutation back to original rows is kept;
  * per query, the candidate set inside each block is then a contiguous
    ``[start, start + len)`` row range, found host-side by ``searchsorted``;
  * the banded ``topk_hamming`` kernel scans only the tiles covering those
    ranges, and merged indices are translated back through the permutation.

Everything here is host-side numpy: it runs once per batch, before the
jitted search.

Streaming ingestion reuses all of it unchanged: a tenant's appended rows
get their own small sorted block (:class:`~repro.serve.delta.DeltaBank`),
and the merged base+delta search builds a *merged* ``PrecursorIndex``
over the concatenated per-block masses — ``candidate_ranges`` against it
yields exactly the window ranges a from-scratch rebuild would plan.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.kernels.block_utils import band_tiles


@dataclasses.dataclass(frozen=True)
class OMSConfig:
    """Tolerance conventions for the precursor window (Dalton-like units,
    whatever the bank's precursor column is measured in).

    ``open_search=True``: accept ``query - ref`` in the open interval
    ``(-tol, open_tol)``. ``open_search=False``: plain exact search,
    ``|query - ref| < tol``.
    """

    tol: float = 20.0
    open_tol: float = 200.0
    open_search: bool = True

    def window(self, query_prec: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Per-query (lo, hi) bounds: references strictly inside (lo, hi)."""
        q = np.asarray(query_prec, np.float32)
        lo = q - (self.open_tol if self.open_search else self.tol)
        hi = q + self.tol
        return lo, hi


@dataclasses.dataclass(frozen=True)
class PrecursorIndex:
    """Blockwise precursor-sorted layout of a [decoys; targets] bank.

    ``prec_sorted`` is ascending *within each block* (block boundaries in
    ``block_bounds``); ``perm[i]`` is the original bank row now stored at
    sorted row ``i``. Blocks are sorted independently so decoy rows keep
    smaller indices than every target row — the global index order that the
    shard merge's tie-break (lowest index wins) relies on to resolve exact
    score ties to the decoy.
    """

    prec_sorted: np.ndarray       # (num_rows,) float32
    perm: np.ndarray              # (num_rows,) int32 sorted row -> original row
    block_bounds: tuple[int, ...]  # e.g. (0, num_decoys, num_rows)

    @property
    def num_rows(self) -> int:
        return int(self.prec_sorted.shape[0])

    @property
    def num_blocks(self) -> int:
        return len(self.block_bounds) - 1

    def candidate_ranges(self, query_prec: np.ndarray, cfg: OMSConfig
                         ) -> tuple[np.ndarray, np.ndarray]:
        """Per-query per-block candidate row ranges in the sorted layout.

        Returns ``(starts, lens)``, both (num_blocks, Q) int32. The range of
        block b for query q is ``[starts[b, q], starts[b, q] + lens[b, q])``
        and selects exactly the rows ``candidate_window_mask`` would keep:
        strict bounds on both sides.
        """
        lo, hi = cfg.window(query_prec)
        starts, lens = [], []
        for b in range(self.num_blocks):
            b0, b1 = self.block_bounds[b], self.block_bounds[b + 1]
            block = self.prec_sorted[b0:b1]
            # first row with prec > lo / first row with prec >= hi
            s = b0 + np.searchsorted(block, lo, side="right")
            e = b0 + np.searchsorted(block, hi, side="left")
            starts.append(s)
            lens.append(np.maximum(e - s, 0))
        return (np.stack(starts).astype(np.int32),
                np.stack(lens).astype(np.int32))


def build_precursor_index(precursor: np.ndarray,
                          decoy_precursor: np.ndarray | None = None
                          ) -> PrecursorIndex:
    """Sort the [decoys; targets] bank blockwise by precursor.

    ``precursor`` is the target block's masses; ``decoy_precursor`` the decoy
    block's (decoys generated by m/z-reversal keep their target's precursor,
    so callers usually pass the same array). With no decoys, the bank is a
    single target block.
    """
    tgt = np.asarray(precursor, np.float32)
    blocks = [tgt] if decoy_precursor is None else [
        np.asarray(decoy_precursor, np.float32), tgt]
    bounds = [0]
    prec_parts, perm_parts = [], []
    for block in blocks:
        order = np.argsort(block, kind="stable").astype(np.int32)
        prec_parts.append(block[order])
        perm_parts.append(order + bounds[-1])
        bounds.append(bounds[-1] + block.shape[0])
    return PrecursorIndex(
        prec_sorted=np.concatenate(prec_parts) if prec_parts else
        np.zeros((0,), np.float32),
        perm=np.concatenate(perm_parts) if perm_parts else
        np.zeros((0,), np.int32),
        block_bounds=tuple(bounds),
    )


@dataclasses.dataclass(frozen=True)
class OMSPlan:
    """Host-side per-batch plan: candidate ranges plus the static tile count
    the banded kernel needs.

    ``num_tiles`` is bucketed to a power of two so repeated batches with
    similar window spans reuse the same jit signature. Within that grid
    each (band, query block) scans only the tiles its windows span
    (:func:`repro.kernels.block_utils.band_tiles`). ``scanned_fraction``
    is the work the banded kernel actually does relative to a full scan
    (tiles visited per query block, summed over bands, / total tiles),
    ``budget_fraction`` the same for the static grid (``num_tiles`` per
    band and block), and ``candidate_fraction`` the selectivity of the
    window itself (candidate rows / bank rows).
    """

    starts: np.ndarray       # (B, Q) int32, sorted-layout rows
    lens: np.ndarray         # (B, Q) int32
    num_tiles: int           # static per-band tile count for the kernel
    scanned_fraction: float
    candidate_fraction: float
    budget_fraction: float

    @property
    def has_candidate(self) -> np.ndarray:
        """(Q,) bool — at least one candidate row across all blocks."""
        return self.lens.sum(axis=0) > 0


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _next_pow2(x: int) -> int:
    n = 1
    while n < x:
        n *= 2
    return n


def plan_candidates(index: PrecursorIndex, query_prec: np.ndarray,
                    cfg: OMSConfig, *, num_rows_padded: int,
                    block_q: int = 128, block_r: int = 128) -> OMSPlan:
    """Compute the banded-kernel plan for one query batch.

    ``num_rows_padded`` is the bank's padded row count (shards *
    shard_rows); ``block_q``/``block_r`` must match the tile sizes the
    kernel wrapper will choose. The returned ``num_tiles`` covers, for every
    band and every query block, the tiles from ``floor(min start / block_r)``
    to ``ceil(max end / block_r)`` — the invariant the banded kernel's
    tile-base prefetch relies on. ``scanned_fraction`` counts the tiles the
    kernel visits: per (band, block), from the block's clipped tile base to
    its highest end, as the kernel wrapper computes them on the device.
    """
    starts, lens = index.candidate_ranges(query_prec, cfg)
    num_bands, q = starts.shape
    nr = index.num_rows
    total_tiles = max(1, _round_up(max(num_rows_padded, 1), block_r) // block_r)
    bq = min(block_q, _round_up(max(q, 1), 8))
    # edge-pad to whole query blocks, as the kernel wrappers do
    pad = ((0, 0), (0, (-q) % bq))
    s = np.pad(starts, pad, mode="edge")
    e = np.pad(starts + lens, pad, mode="edge")
    lo = s.reshape(num_bands, -1, bq).min(axis=2) // block_r
    hi = -(-e.reshape(num_bands, -1, bq).max(axis=2) // block_r)
    span = int((hi - lo).max(initial=1))
    num_tiles = min(_next_pow2(span), total_tiles)
    _, counts = band_tiles(s.ravel(), e.ravel(), block_q=bq, block_r=block_r,
                           total_tiles=total_tiles, num_tiles=num_tiles)
    n_blocks = max(s.shape[1] // bq, 1)
    cand = float(lens.sum()) / max(q * max(nr, 1), 1)
    budget = min(1.0, num_bands * num_tiles / total_tiles)
    scanned = min(1.0, int(counts.sum()) / (n_blocks * total_tiles))
    return OMSPlan(starts=starts, lens=lens, num_tiles=num_tiles,
                   scanned_fraction=scanned, candidate_fraction=cand,
                   budget_fraction=budget)


def translate_indices(index: PrecursorIndex, idx: np.ndarray) -> np.ndarray:
    """Map sorted-layout rows back to original bank rows (numpy variant;
    the serving path uses the jnp gather inline). Rows outside the bank
    (padding fillers) pass through unchanged."""
    idx = np.asarray(idx)
    ok = (idx >= 0) & (idx < index.num_rows)
    return np.where(ok, index.perm[np.clip(idx, 0, max(index.num_rows - 1, 0))],
                    idx)
