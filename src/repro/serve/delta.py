"""Append-only delta banks: streaming ingestion for the serving stack.

A tenant's base bank is the heavy artifact — bit-packed, precursor-sorted,
row-sharded over the mesh, behind a jit cache keyed on its geometry.
Rebuilding it per append would make ingestion O(bank) per spectrum. Instead
new refs (and decoys) land in a small **unpacked single-shard delta bank**
(:class:`DeltaBank`) that is cheap to rebuild per append, and search runs an
exact merged top-k over base + delta:

  * each side runs its own local-top-k/merge pipeline unchanged (the PR 2
    shard machinery — the delta is effectively one extra, unpacked shard);
  * every candidate's index is translated into the row numbering the bank
    *would* have after a from-scratch rebuild over the concatenated arrays
    (``[base decoys; delta decoys; base targets; delta targets]``, each
    block re-sorted by precursor for OMS banks);
  * the two candidate blocks merge by ``(score desc, rebuilt row asc)`` —
    a two-key :func:`jax.lax.sort`, because rebuilt rows *interleave*
    across the sides (a delta decoy sits between base decoys and base
    targets), so the positional tie-break of the shard-merge
    (``_merge_topk``) does not apply across sides.

Both translations are strictly increasing (appended rows keep their
relative order inside each block, and a stable blockwise sort of the
concatenated precursors keeps base rows ahead of delta rows on mass ties),
so each side's top-k — re-keyed by rebuilt rows — is exactly the rebuilt
bank's top-k restricted to that side. Any rebuilt winner therefore appears
among the ``2k`` merged candidates, and the two-key merge reproduces the
rebuilt result **bit-identically**, tie order and (for OMS) sentinel
overflow slots included: the OMS path merges *sorted-layout* rows, then
runs the very same ``canonicalize_overflow_slots`` + permutation translate
a rebuilt bank's ``_oms_finish`` would, against the merged precursor index
and the merged window ranges.

Score scale is shared by construction: the unpacked delta scores int8 dot
products and the packed base scores ``2*hamming - D`` — equal integers for
bipolar HVs — so cross-side comparisons are exact.

:meth:`repro.serve.cache.BankRegistry.compact` folds the delta back into
the bit-packed base past a size threshold; by the identity above, results
are unchanged across the swap.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hd.similarity import dot_similarity
from repro.serve.oms import OMSConfig, OMSPlan, PrecursorIndex, \
    build_precursor_index


@dataclasses.dataclass(frozen=True)
class MergedLayout:
    """Index maps from per-side storage rows into the rebuilt bank's rows.

    ``b_map``/``d_map`` take a base/delta *storage* row (original row for
    plain banks, sorted-layout row for OMS banks) to the storage row the
    same HV would occupy after a from-scratch rebuild over the
    concatenated arrays. Both maps are strictly increasing — the property
    that lets each side's own ascending-index tie-break stand in for the
    rebuilt bank's.
    """

    num_rows: int
    num_decoys: int
    b_map: np.ndarray              # (base.num_rows,) int32
    d_map: np.ndarray              # (delta.num_rows,) int32
    index: PrecursorIndex | None   # merged OMS index (None for plain banks)


class DeltaBank:
    """Append-only unpacked delta rows for one tenant.

    Appended refs/decoys accumulate host-side; after every append the
    small single-shard, never-packed :class:`ShardedDatabase` (``self.db``)
    is rebuilt — O(delta), not O(bank). For OMS tenants the delta carries
    its own precursor-sorted index, and :meth:`layout` caches the maps
    into the merged (rebuilt-equivalent) row space.
    """

    def __init__(self, dim: int, *, oms: bool):
        self.dim = int(dim)
        self.oms = bool(oms)
        self.refs = np.zeros((0, self.dim), np.int8)
        self.decoys = np.zeros((0, self.dim), np.int8)
        self.precursor = np.zeros((0,), np.float32)
        self.decoy_precursor = np.zeros((0,), np.float32)
        self.version = 0
        self.db = None
        self.storage = np.zeros((0, self.dim), np.int8)
        self._layout: MergedLayout | None = None
        self._layout_key = None

    @property
    def num_targets(self) -> int:
        return int(self.refs.shape[0])

    @property
    def num_decoys(self) -> int:
        return int(self.decoys.shape[0])

    @property
    def num_rows(self) -> int:
        return self.num_targets + self.num_decoys

    def append(self, refs, decoys=None, *, precursor=None,
               decoy_precursor=None) -> int:
        """Land one batch of refs (+ optional decoys) in the delta; returns
        the delta's total row count. OMS deltas require per-ref precursor
        masses (``decoy_precursor`` defaults to ``precursor`` when the
        decoy count matches, mirroring ``shard_database``)."""
        r = np.asarray(refs, np.int8)
        if r.size == 0:
            r = np.zeros((0, self.dim), np.int8)
        if r.ndim != 2 or r.shape[1] != self.dim:
            raise ValueError(f"appended refs shape {r.shape} != "
                             f"(n, {self.dim})")
        d = None
        if decoys is not None:
            d = np.asarray(decoys, np.int8)
            if d.ndim != 2 or d.shape[1] != self.dim:
                raise ValueError(f"appended decoys shape {d.shape} != "
                                 f"(n, {self.dim})")
        n_new = r.shape[0] + (0 if d is None else d.shape[0])
        if n_new == 0:
            raise ValueError("append needs at least one ref or decoy row")
        if self.oms:
            if precursor is None:
                raise ValueError("this tenant's bank is precursor-sorted "
                                 "(OMS); append requires precursor=")
            prec = np.asarray(precursor, np.float32).reshape(-1)
            if prec.shape[0] != r.shape[0]:
                raise ValueError(f"precursor has {prec.shape[0]} entries "
                                 f"for {r.shape[0]} appended refs")
            dprec = None
            if d is not None:
                dprec = (prec if decoy_precursor is None
                         else np.asarray(decoy_precursor,
                                         np.float32).reshape(-1))
                if dprec.shape[0] != d.shape[0]:
                    raise ValueError(
                        f"decoy_precursor has {dprec.shape[0]} entries for "
                        f"{d.shape[0]} appended decoys")
        else:
            if precursor is not None or decoy_precursor is not None:
                raise ValueError("this tenant's bank has no precursor "
                                 "index; append must not pass precursor=")
            prec = dprec = None

        self.refs = np.concatenate([self.refs, r])
        if d is not None:
            self.decoys = np.concatenate([self.decoys, d])
        if self.oms:
            self.precursor = np.concatenate([self.precursor, prec])
            if dprec is not None:
                self.decoy_precursor = np.concatenate(
                    [self.decoy_precursor, dprec])
        self.version += 1
        self._rebuild()
        return self.num_rows

    def _rebuild(self) -> None:
        from repro.serve.db_search import shard_database
        decoys = self.decoys if self.num_decoys else None
        self.db = shard_database(
            self.refs, decoys=decoys, pack=False,
            precursor=self.precursor if self.oms else None,
            decoy_precursor=(self.decoy_precursor
                             if self.oms and decoys is not None else None))
        # storage-order rows for the fused merged-search tail: the bank
        # layout is decoys-then-targets, precursor-sorted for OMS banks
        # (``oms.perm`` maps sorted row -> original row)
        rows = (np.concatenate([self.decoys, self.refs])
                if self.num_decoys else self.refs)
        self.storage = rows[self.db.oms.perm] if self.oms else rows

    def layout(self, base) -> MergedLayout:
        """The (cached) rebuilt-row maps for this delta against ``base``.

        Keyed on the delta version and base geometry only: an evicted-and-
        rebuilt base is content-identical, so the maps survive it.
        """
        key = (self.version, base.num_rows, base.num_decoys)
        if self._layout is None or self._layout_key != key:
            self._layout = merged_layout(base, self)
            self._layout_key = key
        return self._layout


def merged_layout(base, delta: DeltaBank) -> MergedLayout:
    """Compute the rebuilt-row maps (see :class:`MergedLayout`)."""
    nd0, ndd = base.num_decoys, delta.num_decoys
    nt0 = base.num_targets
    n_m = base.num_rows + delta.num_rows
    b_orig = np.arange(base.num_rows, dtype=np.int32)
    b_trans = np.where(b_orig < nd0, b_orig, b_orig + ndd).astype(np.int32)
    d_orig = np.arange(delta.num_rows, dtype=np.int32)
    d_trans = np.where(d_orig < ndd, d_orig + nd0,
                       d_orig + nd0 + nt0).astype(np.int32)
    if base.oms is None:
        return MergedLayout(num_rows=n_m, num_decoys=nd0 + ndd,
                            b_map=b_trans, d_map=d_trans, index=None)
    # original-order base precursors, recovered exactly from the sorted
    # index (float32 round-trips, so this matches whatever register()
    # passed — including the decoy default)
    base_prec = np.empty(base.num_rows, np.float32)
    base_prec[base.oms.perm] = base.oms.prec_sorted
    tgt = np.concatenate([base_prec[nd0:], delta.precursor])
    dec = np.concatenate([base_prec[:nd0], delta.decoy_precursor])
    index = build_precursor_index(tgt, dec if dec.shape[0] else None)
    pos = np.empty(n_m, np.int32)
    pos[index.perm] = np.arange(n_m, dtype=np.int32)
    return MergedLayout(
        num_rows=n_m, num_decoys=nd0 + ndd,
        b_map=pos[b_trans[base.oms.perm]].astype(np.int32),
        d_map=pos[d_trans[delta.db.oms.perm]].astype(np.int32),
        index=index)


def _merge_by_row(cand_vals, cand_rows, k: int):
    """Top-k over candidate blocks by ``(score desc, rebuilt row asc)``.

    The cross-side twin of ``_merge_topk``: rebuilt rows interleave across
    the base/delta blocks, so the tie-break must sort on the translated
    row itself, not block position. Scores are int32 bounded by ±D, so the
    negated-float32 primary key is exact; sentinel slots map to +inf and
    sort behind every real candidate (their payload value stays sentinel
    for the caller's overflow canonicalization).
    """
    from repro.serve.db_search import _SENTINEL
    key = jnp.where(cand_vals == _SENTINEL, jnp.float32(jnp.inf),
                    -cand_vals.astype(jnp.float32))
    _, rows, vals = jax.lax.sort(
        (key, cand_rows.astype(jnp.int32), cand_vals), num_keys=2)
    return rows[..., :k], vals[..., :k]


@functools.partial(jax.jit, static_argnames=("k", "kd"))
def _merged_tail(delta_rows, q_raw, bi, bv, b_map, d_map, *, k: int,
                 kd: int):
    """Everything after the base search, fused into ONE jitted dispatch.

    The delta is small by construction, so the dominant cost of searching
    it through the generic per-shard pipeline is fixed eager-op dispatch
    overhead, not math — enough to drag the merged path well below the
    pure-base qps the bench floor guards. Here the delta scores
    (``dot_similarity``, the exact int32 scale ``_local_scores`` uses on
    unpacked banks), its ``lax.top_k`` (ties break to the lowest storage
    row, and ``d_map`` is strictly increasing, so rebuilt-row order is
    preserved — the same argument as the staged pipeline's), both row
    translations, and the cross-side merge compile into a single call.
    ``delta_rows`` holds exactly the delta's storage rows (no shard
    padding), so no sentinel masking is needed on that side; base
    overflow slots clip into ``b_map``'s range with their sentinel
    values intact, exactly as before.
    """
    scores = dot_similarity(q_raw, delta_rows)
    # top-kd by iterative masked argmax rather than lax.top_k: the CPU
    # top-k custom call sorts entire rows (~ms for a few hundred columns),
    # while kd is tiny. argmax ties to the lowest index and each winner is
    # masked below any real score (bounded by ±D), so the (value desc,
    # row asc) order is bit-identical to lax.top_k's.
    s = scores
    cols = jnp.arange(s.shape[1], dtype=jnp.int32)[None, :]
    dvs, dis = [], []
    for _ in range(kd):
        i = jnp.argmax(s, axis=1).astype(jnp.int32)
        dvs.append(jnp.take_along_axis(s, i[:, None], axis=1))
        dis.append(i[:, None])
        s = jnp.where(cols == i[:, None], jnp.iinfo(jnp.int32).min, s)
    dv = jnp.concatenate(dvs, axis=1)
    di = jnp.concatenate(dis, axis=1)
    b_rows = jnp.take(b_map, jnp.clip(bi, 0, b_map.shape[0] - 1), axis=0)
    d_rows = jnp.take(d_map, di, axis=0)
    return _merge_by_row(jnp.concatenate([bv, dv], axis=1),
                         jnp.concatenate([b_rows, d_rows], axis=1), k)


def merged_search_encoded(base, delta: DeltaBank, q_enc, q_raw, k: int
                          ) -> tuple[jax.Array, jax.Array]:
    """Exact top-k over base + delta, bit-identical to a from-scratch
    rebuild over the concatenated arrays.

    ``q_enc`` is the batch in the *base* bank's storage form (packed or
    int8); ``q_raw`` the same batch as raw bipolar int8 rows for the
    unpacked delta. Returned indices are rebuilt-bank storage rows
    (original rows for plain banks; the sorted layout for OMS banks,
    matching what exact search over a rebuilt OMS bank returns).
    """
    from repro.serve.db_search import search_database_encoded
    layout = delta.layout(base)
    bi, bv = search_database_encoded(base, q_enc, k)
    kd = min(k, delta.num_rows)
    return _merged_tail(jnp.asarray(delta.storage), q_raw, bi, bv,
                        jnp.asarray(layout.b_map),
                        jnp.asarray(layout.d_map), k=k, kd=kd)


@dataclasses.dataclass(frozen=True)
class MergedOMSPlan:
    """Per-batch OMS plan for a base + delta pair.

    Carries each side's own :class:`~repro.serve.oms.OMSPlan` (the delta
    plan runs on the small unpacked bank, full masked path) plus the
    *merged* candidate ranges — identical to the ranges a rebuilt bank's
    plan would hold, since they depend only on the merged precursor index.
    """

    base: OMSPlan
    delta: OMSPlan
    starts: np.ndarray       # (B, Q) int32, merged sorted-layout rows
    lens: np.ndarray         # (B, Q) int32
    candidate_fraction: float
    scanned_fraction: float
    budget_fraction: float

    @property
    def has_candidate(self) -> np.ndarray:
        return self.lens.sum(axis=0) > 0


def merged_oms_plan(base, delta: DeltaBank, query_prec: np.ndarray,
                    cfg: OMSConfig | None = None) -> MergedOMSPlan:
    """Host-side plan for one precursor-sorted query batch against
    base + delta. ``scanned_fraction`` counts the delta as a full scan
    (it is searched unbanded — it's small by construction)."""
    from repro.serve.db_search import oms_plan
    cfg = cfg or OMSConfig()
    layout = delta.layout(base)
    bplan = oms_plan(base, query_prec, cfg)
    dplan = oms_plan(delta.db, query_prec, cfg)
    starts, lens = layout.index.candidate_ranges(
        np.asarray(query_prec), cfg)
    q = max(starts.shape[1], 1)
    cand = float(lens.sum()) / max(q * max(layout.num_rows, 1), 1)
    base_padded = base.num_shards * base.shard_rows
    total = max(base_padded + delta.db.num_rows, 1)

    def with_delta(base_fraction: float) -> float:
        return min(1.0, (base_fraction * base_padded
                         + delta.db.num_rows) / total)

    return MergedOMSPlan(base=bplan, delta=dplan, starts=starts, lens=lens,
                         candidate_fraction=cand,
                         scanned_fraction=with_delta(bplan.scanned_fraction),
                         budget_fraction=with_delta(bplan.budget_fraction))


def merged_oms_search_encoded(base, delta: DeltaBank, q_enc, q_raw,
                              mplan: MergedOMSPlan, k: int
                              ) -> tuple[jax.Array, jax.Array]:
    """OMS top-k over base + delta, bit-identical to a rebuilt bank.

    Each side runs its inner (pre-canonicalization) OMS route against its
    own index; candidates merge in the *merged sorted layout*, then the
    shared overflow-canonicalize + perm-translate tail runs against the
    merged index and window ranges — the same two steps a rebuilt bank's
    ``_oms_finish`` applies. Returned indices are original merged-bank
    rows (delta decoys land after base decoys, delta targets after base
    targets).
    """
    from repro.kernels.topk_hamming import canonicalize_overflow_slots
    from repro.serve.db_search import _oms_search_inner
    layout = delta.layout(base)
    bi, bv = _oms_search_inner(base, q_enc, mplan.base, k)
    kd = min(k, delta.db.num_rows)
    di, dv = _oms_search_inner(delta.db, q_raw, mplan.delta, kd)
    # kernel overflow fillers may point past the (padded) bank; clip before
    # the map gather — their values are sentinel, so the merge ranks them
    # behind every real candidate and canonicalization rewrites them
    b_rows = jnp.take(jnp.asarray(layout.b_map),
                      jnp.clip(bi, 0, base.num_rows - 1), axis=0)
    d_rows = jnp.take(jnp.asarray(layout.d_map),
                      jnp.clip(di, 0, delta.db.num_rows - 1), axis=0)
    rows, vals = _merge_by_row(jnp.concatenate([bv, dv], axis=1),
                               jnp.concatenate([b_rows, d_rows], axis=1), k)
    starts = jnp.asarray(mplan.starts, jnp.int32)
    ends = starts + jnp.asarray(mplan.lens, jnp.int32)
    s_c = jnp.clip(starts, 0, layout.num_rows)
    e_c = jnp.clip(ends, s_c, layout.num_rows)
    rows = canonicalize_overflow_slots(rows, vals, s_c, e_c, layout.num_rows)
    idx = jnp.take(jnp.asarray(layout.index.perm), rows, axis=0)
    return idx, vals
