"""Sharded HD database search: local top-k per shard, global top-k merge.

The reference bank (targets + decoys, bipolar HVs) is sharded row-wise
over the ``model`` mesh axis; queries are batched over ``data``. Each
shard scores its ``R/n`` rows — via the bit-packed XOR+popcount path when
``D % 32 == 0`` (:func:`repro.core.hd.similarity.topk_search_packed`'s
kernel), else the int matmul — keeps its local ``lax.top_k``, and only
the ``Q x k`` candidate (index, score) pairs per shard cross the
interconnect (``all_gather`` over ``model``), never the full ``Q x R``
score matrix. A second ``lax.top_k`` over the ``Q x (n*k)`` gathered
candidates produces the global result.

**Fused per-shard search.** With ``shard_database(..., fused=True)`` the
per-shard score-then-top-k pair is replaced by the streaming Pallas
kernel (:mod:`repro.kernels.topk_hamming`): score tiles stay in VMEM and
the running top-k is carried across reference tiles in scratch, so even
*per shard* the ``Q x R/n`` score matrix never reaches HBM — candidate
traffic is O(Q·k) end to end. The kernel reproduces ``lax.top_k``
tie-breaking exactly, so every bit-identity invariant below holds
unchanged on the fused path (the global k-merge is shared code).

**Bit-identity with the unsharded oracle.** ``lax.top_k`` breaks ties
toward the lower position. Each shard's local top-k orders tied scores by
ascending local (hence global) index, and the gather concatenates shard
blocks in ascending shard-offset order, so within any tied score the
gathered candidates appear in ascending *global* index order — the merge
therefore selects exactly the rows the unsharded
:func:`repro.core.hd.similarity.topk_search` would. A row pruned by its
shard's local top-k is beaten by k rows of the same shard and so can
never appear in the global top-k. Ragged banks are padded to equal shard
sizes and padding columns are masked to ``INT32_MIN`` (strictly below any
real score, which is bounded by ``-D``).

**Degradation.** With no mesh (or a size-1 ``model`` axis) everything
falls back to the single-device ``topk_search`` path; a query batch not
divisible by the ``data`` axis is replicated instead of batch-sharded —
same contract as ``repro.dist.sharding``.

**FDR routing.** The bank stores decoys *before* targets so that on a
target/decoy score tie the decoy (lower row) wins the merged top-1 —
exactly the conservative ``best_target > best_decoy`` competition of
``repro.core.pipeline.run_db_search`` — and the rank-0 candidate alone
determines the competition outcome fed to ``repro.spectra.fdr``.

**Serving layer.** :class:`DBSearchServer` runs the host-side loop:
tenant-homogeneous micro-batches out of
:class:`~repro.serve.queue.MicroBatchQueue`, per-tenant banks out of a
:class:`~repro.serve.cache.BankRegistry` (lazy shard-on-first-use, LRU),
query encodes memoized in a :class:`~repro.serve.cache.QueryHVCache`,
and batch shapes padded to a bounded bucket ladder so tenant switches
reuse the jit cache instead of recompiling. Device work runs behind the
:class:`SearchExecutor` dispatch/poll/finalize seam, shared by the
synchronous flush loop and the continuous-batching scheduler
(:mod:`repro.serve.scheduler`); with a :class:`QueryEncoder` the server
additionally accepts *raw quantized spectra* and encodes on the device —
staged, or as one fused encode->pack->search kernel dispatch per shard
(:mod:`repro.kernels.encode_search`, ``fused_e2e=True``).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.hd.encoding import (
    HDEncoderConfig,
    encode_levels_batch,
    make_codebooks,
)
from repro.core.hd.similarity import (
    bitpack_bipolar,
    dot_similarity,
    hamming_similarity_packed,
    topk_search,
)
from repro.kernels.block_utils import validate_block
from repro.serve import trace
from repro.serve.cache import BankRegistry, QueryHVCache
from repro.serve.clustering import ClusteringConfig, StreamingClusterer
from repro.serve.oms import (
    OMSConfig,
    OMSPlan,
    PrecursorIndex,
    build_precursor_index,
    plan_candidates,
)
from repro.serve.queue import LatencyStats, MicroBatchQueue, Request
from repro.serve.scheduler import ContinuousScheduler
from repro.spectra.fdr import fdr_filter

_SENTINEL = jnp.iinfo(jnp.int32).min
_OMS_ALIGN = 128  # shard_rows alignment for OMS banks (= kernel block_r), so
                  # shard bases stay tile-aligned and per-shard band spans
                  # never exceed the host-side plan's tile budget
_OMS_BLOCK_Q = 8  # banded-kernel Q-block: the tile budget is per Q block, so
                  # narrow blocks of precursor-adjacent queries (the server
                  # sorts each batch) keep the scanned span near the window
                  # width instead of the batch's full mass spread


# --------------------------------------------------------------------------
# per-shard compute + merge (pure; shared by shard_map and the emulated path)
# --------------------------------------------------------------------------

def _local_scores(queries, refs_local, *, dim: int, packed: bool) -> jax.Array:
    """(Q, *) x (Rl, *) -> (Q, Rl) int32 dot-product-scale scores."""
    if packed:
        # 2 * hamming_sim - dim == <q, r> for bipolar HVs, exactly
        return 2 * hamming_similarity_packed(queries, refs_local, dim) - dim
    return dot_similarity(queries, refs_local)


def _local_topk(scores, base, k: int, num_rows: int):
    """Per-shard top-k with padding mask and global index translation.

    base: this shard's first global row (int). Padding columns (global row
    >= num_rows) are masked to a sentinel below any real score.
    Returns (vals (Q, k), global_idx (Q, k)).
    """
    shard_rows = scores.shape[-1]
    col = base + jnp.arange(shard_rows, dtype=jnp.int32)
    scores = jnp.where(col[None, :] < num_rows, scores, _SENTINEL)
    vals, local_idx = jax.lax.top_k(scores, k)
    return vals, local_idx.astype(jnp.int32) + base


def _local_topk_fused(queries, refs_local, base, k: int, num_rows: int,
                      dim: int, block_q: int | None = None,
                      block_r: int | None = None,
                      word_chunk: int | None = None):
    """Fused twin of ``_local_scores`` + ``_local_topk``: the streaming
    Pallas kernel computes tile scores and keeps the running top-k in
    VMEM, so this shard's (Q, Rl) score matrix never reaches HBM.

    base may be a python int (emulated shards) or a traced scalar (the
    shard_map path); the kernel masks rows past ``num_rows - base`` to
    the same sentinel ``_local_topk`` uses, and returns local indices
    that translate to global rows by adding ``base`` — bit-identical to
    the unfused pair, tie order included. Block overrides (the bank's
    ``shard_database(..., block_q=...)`` settings) pass straight to the
    kernel; None defers to the tuning table / defaults.
    """
    # deferred like similarity.topk_search_packed: the kernel package is
    # only pulled in when a fused bank is actually searched
    from repro.kernels.topk_hamming import topk_hamming_pallas
    shard_rows = refs_local.shape[0]
    num_valid = jnp.clip(jnp.asarray(num_rows - base, jnp.int32),
                         0, shard_rows)
    idx, vals = topk_hamming_pallas(queries, refs_local, dim=dim, k=k,
                                    num_valid=num_valid, block_q=block_q,
                                    block_r=block_r, word_chunk=word_chunk)
    return vals, idx + jnp.asarray(base, jnp.int32)


def _merge_topk(cand_vals, cand_idx, k: int):
    """Global top-k over gathered per-shard candidates (Q, n*k).

    Candidate blocks must be concatenated in ascending shard order so the
    positional tie-break reproduces the global ascending-index tie-break.
    Returns (idx (Q, k), vals (Q, k)) — the ``topk_search`` contract.
    """
    vals, pos = jax.lax.top_k(cand_vals, k)
    idx = jnp.take_along_axis(cand_idx, pos, axis=-1)
    return idx, vals


def _local_oms_topk(q_enc, refs_local, base, k: int, num_rows: int, dim: int,
                    packed: bool, starts, ends):
    """Unfused per-shard OMS top-k: full local scores, sentinel-masked
    outside every query's per-block band (global sorted-layout rows in
    ``starts``/``ends``, each (B, Q)) and past ``num_rows``.

    This *is* the masked-full-matrix oracle restricted to one shard — the
    banded kernel below must match it bit-exactly.
    """
    scores = _local_scores(q_enc, refs_local, dim=dim, packed=packed)
    shard_rows = refs_local.shape[0]
    col = (jnp.asarray(base, jnp.int32)
           + jnp.arange(shard_rows, dtype=jnp.int32))[None, :]
    band = jnp.zeros(scores.shape, bool)
    for b in range(starts.shape[0]):  # static B (1 or 2) bands per query
        band = band | ((col >= starts[b][:, None]) & (col < ends[b][:, None]))
    scores = jnp.where(band & (col < num_rows), scores, _SENTINEL)
    vals, local_idx = jax.lax.top_k(scores, k)
    return vals, local_idx.astype(jnp.int32) + jnp.asarray(base, jnp.int32)


def _local_oms_topk_fused(q_enc, refs_local, base, k: int, num_rows: int,
                          dim: int, starts, ends, num_tiles: int):
    """Banded-kernel twin of ``_local_oms_topk``: one kernel launch per
    band (decoy block, target block), each scanning only ``num_tiles`` R
    tiles around that band, then a local merge over the 2k candidates.

    Band blocks concatenate in ascending global-row order (decoy rows
    precede target rows in the sorted layout) so the merge's positional
    tie-break keeps the global ascending-index tie-break. Overflow slots
    keep their kernel fillers — sentinel-valued, overwritten by the
    caller's global canonicalization — hence ``canonicalize=False``.
    """
    from repro.kernels.topk_hamming import topk_hamming_banded_pallas
    shard_rows = refs_local.shape[0]
    nv = jnp.clip(jnp.asarray(num_rows - base, jnp.int32), 0, shard_rows)
    vals_blocks, idx_blocks = [], []
    for b in range(starts.shape[0]):
        s_l = jnp.clip(starts[b] - base, 0, shard_rows).astype(jnp.int32)
        e_l = jnp.clip(ends[b] - base, s_l, shard_rows).astype(jnp.int32)
        idx, vals = topk_hamming_banded_pallas(
            q_enc, refs_local, s_l, e_l - s_l, dim=dim, k=k, num_valid=nv,
            num_tiles=num_tiles, block_q=_OMS_BLOCK_Q, canonicalize=False)
        vals_blocks.append(vals)
        idx_blocks.append(idx + jnp.asarray(base, jnp.int32))
    if len(vals_blocks) == 1:
        return vals_blocks[0], idx_blocks[0]
    idx, vals = _merge_topk(jnp.concatenate(vals_blocks, axis=1),
                            jnp.concatenate(idx_blocks, axis=1), k)
    return vals, idx


def _local_oms(q_enc, refs_local, base, k: int, num_rows: int, dim: int,
               packed: bool, fused: bool, starts, ends, num_tiles: int):
    """Per-shard OMS top-k, fused or unfused. Returns (vals, global_idx)."""
    if fused:
        return _local_oms_topk_fused(q_enc, refs_local, base, k, num_rows,
                                     dim, starts, ends, num_tiles)
    return _local_oms_topk(q_enc, refs_local, base, k, num_rows, dim,
                           packed, starts, ends)


# --------------------------------------------------------------------------
# sharded database
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedDatabase:
    """A reference bank prepared for sharded search.

    data holds ``num_shards * shard_rows`` rows (zero-padded past
    ``num_rows``), bit-packed to uint32 words when ``packed``; rows
    ``[0, num_decoys)`` are decoys, ``[num_decoys, num_rows)`` targets.

    With ``oms`` set (the bank was built with ``precursor=``), each block
    is stored sorted by precursor mass and ``oms.perm`` maps sorted rows
    back to original block rows — search results from the OMS routes are
    translated before they leave :func:`oms_search_encoded`, so callers
    always see original row numbering. ``oms_perm`` is that permutation
    on the device (replicated over the mesh), kept from the build's
    sorting gather so no search uploads it again.
    """

    data: jax.Array
    num_rows: int
    num_decoys: int
    dim: int
    shard_rows: int
    packed: bool
    mesh: Mesh | None
    axis: str
    emulated_shards: int = 1
    fused: bool = False
    oms: PrecursorIndex | None = None
    oms_perm: jax.Array | None = None
    # explicit per-bank kernel tile overrides for the fused paths; None
    # defers to the active tuning table / defaults at trace time
    block_q: int | None = None
    block_r: int | None = None
    word_chunk: int | None = None

    @property
    def num_targets(self) -> int:
        return self.num_rows - self.num_decoys

    @property
    def num_shards(self) -> int:
        if self.mesh is None or self.axis not in self.mesh.shape:
            return self.emulated_shards
        return self.mesh.shape[self.axis]


def shard_database(refs: jax.Array, *, decoys: jax.Array | None = None,
                   mesh: Mesh | None = None, axis: str = "model",
                   pack: bool | str = "auto",
                   emulate_shards: int | None = None,
                   fused: bool = False,
                   precursor: np.ndarray | None = None,
                   decoy_precursor: np.ndarray | None = None,
                   block_q: int | None = None,
                   block_r: int | None = None,
                   word_chunk: int | None = None
                   ) -> ShardedDatabase:
    """Build a :class:`ShardedDatabase` from bipolar (R, D) reference HVs,
    or from already bit-packed (R, D/32) uint32 words (D = 32 words).

    decoys: optional decoy HVs in the same form as ``refs``, stored
      *before* the targets (see module docstring for why the order
      matters).
    pack: True / False / "auto" (bit-pack whenever D % 32 == 0); packed
      inputs stay packed. Each block is brought to its storage form before
      the blocks are joined, so an unpacked bank never exists whole.
    emulate_shards: with no mesh, pad/slice the bank as if it were split
      into this many shards and run the identical local-top-k/merge
      pipeline shard-by-shard on one device — the tier-1 stand-in for the
      shard_map path (mutually exclusive with a >1 ``axis`` mesh).
    fused: route per-shard search through the streaming top-k Pallas
      kernel (``repro.kernels.topk_hamming``) instead of materializing
      each shard's (Q, R/n) score matrix — bit-identical results; packed
      banks take the XOR+popcount tile path, unpacked banks the int8-dot
      variant.
    precursor: optional (R,) per-target precursor masses — enables the OMS
      routes: each block is stored precursor-sorted (decoys still before
      targets; blocks sort independently so the decoy-wins-ties order
      survives) with the permutation kept for index translation.
    decoy_precursor: per-decoy masses; defaults to ``precursor`` (decoys
      from ``make_decoys`` reverse the m/z axis but keep the mass).
    block_q/block_r/word_chunk: explicit kernel tile sizes for this bank's
      fused search paths (validated here against the TPU tile alignment);
      ``None`` defers to the active tuning table / kernel defaults at
      trace time (:mod:`repro.kernels.block_utils`). The OMS banded
      routes keep their fixed ``block_q``/``block_r`` (the host-side tile
      budget is priced in those units) regardless of these overrides.
    The padded bank is device_put row-sharded over ``axis`` when a mesh
    with that axis (size > 1) is supplied; otherwise it stays local.
    """
    for _name, _val in (("block_q", block_q), ("block_r", block_r),
                        ("word_chunk", word_chunk)):
        if _val is not None:
            validate_block("topk_hamming", _name, _val)
    prepacked = refs.dtype == jnp.uint32
    if prepacked:
        dim = 32 * int(refs.shape[-1])
        if pack is False:
            raise ValueError("pack=False cannot take packed uint32 refs")
        packed = True
    else:
        dim = int(refs.shape[-1])
        if pack == "auto":
            packed = dim % 32 == 0
        else:
            packed = bool(pack)
            if packed and dim % 32 != 0:
                raise ValueError(
                    f"pack=True requires D % 32 == 0, got D={dim}")

    def stored(x):
        if prepacked:
            return x
        return bitpack_bipolar(x) if packed else x.astype(jnp.int8)

    num_decoys = 0
    blocks = [stored(refs)]
    if decoys is not None:
        if ((decoys.dtype == jnp.uint32) != prepacked
                or decoys.shape[-1] != refs.shape[-1]):
            raise ValueError(f"decoy dim {decoys.dtype}{decoys.shape} does "
                             f"not match refs {refs.dtype}{refs.shape}")
        num_decoys = int(decoys.shape[0])
        blocks.insert(0, stored(decoys))
    store = blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks)
    del blocks
    num_rows = int(store.shape[0])

    oms_index = oms_perm = None
    if precursor is not None:
        prec = np.asarray(precursor, np.float32).reshape(-1)
        if prec.shape[0] != int(refs.shape[0]):
            raise ValueError(
                f"precursor has {prec.shape[0]} entries for "
                f"{int(refs.shape[0])} refs")
        dprec = None
        if decoys is not None:
            dprec = prec if decoy_precursor is None else np.asarray(
                decoy_precursor, np.float32).reshape(-1)
            if dprec.shape[0] != num_decoys:
                raise ValueError(
                    f"decoy_precursor has {dprec.shape[0]} entries for "
                    f"{num_decoys} decoys")
        oms_index = build_precursor_index(prec, dprec)
        oms_perm = jnp.asarray(oms_index.perm)
        store = store[oms_perm]

    mesh_n = mesh.shape[axis] if (mesh is not None and axis in mesh.shape) else 1
    emu = int(emulate_shards or 1)
    if emu > 1 and mesh_n > 1:
        raise ValueError("emulate_shards requires no (or size-1) mesh axis")
    n = mesh_n if mesh_n > 1 else emu
    shard_rows = -(-num_rows // n)  # ceil
    if oms_index is not None and n > 1:
        # tile-align shard bases: every shard's clipped band then spans at
        # most as many kernel tiles as the global band does, so one static
        # host-side tile budget covers all shards
        shard_rows = -(-shard_rows // _OMS_ALIGN) * _OMS_ALIGN
    pad_rows = n * shard_rows - num_rows
    if pad_rows:
        store = jnp.pad(store, ((0, pad_rows), (0, 0)))
    if mesh_n > 1:
        store = jax.device_put(store, NamedSharding(mesh, P(axis, None)))
        if oms_perm is not None:
            oms_perm = jax.device_put(oms_perm, NamedSharding(mesh, P()))
    return ShardedDatabase(data=store, num_rows=num_rows, num_decoys=num_decoys,
                           dim=dim, shard_rows=shard_rows, packed=packed,
                           mesh=mesh if mesh_n > 1 else None, axis=axis,
                           emulated_shards=emu if mesh_n == 1 else 1,
                           fused=bool(fused), oms=oms_index,
                           oms_perm=oms_perm, block_q=block_q, block_r=block_r,
                           word_chunk=word_chunk)


@functools.lru_cache(maxsize=None)
def _sharded_search_fn(mesh: Mesh, axis: str, shard_rows: int, num_rows: int,
                       dim: int, packed: bool, k: int, batch_sharded: bool,
                       fused: bool = False,
                       blocks: tuple[int | None, ...] = (None, None, None)):
    """Compile the shard_map search for one (db geometry, k, batch,
    block-override) signature — ``blocks`` is (block_q, block_r,
    word_chunk) and joins the cache key so banks with different explicit
    tiles never share a stale compile."""
    q_spec = P("data", None) if batch_sharded else P(None, None)
    block_q, block_r, word_chunk = blocks

    def body(q, refs_local):
        base = jax.lax.axis_index(axis).astype(jnp.int32) * shard_rows
        if fused:
            vals, gidx = _local_topk_fused(q, refs_local, base, k, num_rows,
                                           dim, block_q=block_q,
                                           block_r=block_r,
                                           word_chunk=word_chunk)
        else:
            scores = _local_scores(q, refs_local, dim=dim, packed=packed)
            vals, gidx = _local_topk(scores, base, k, num_rows)
        # Q x k per shard on the wire — all_gather concatenates the shard
        # blocks in ascending axis order (the tie-break invariant).
        vals_all = jax.lax.all_gather(vals, axis, axis=1, tiled=True)
        idx_all = jax.lax.all_gather(gidx, axis, axis=1, tiled=True)
        return _merge_topk(vals_all, idx_all, k)

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(q_spec, P(axis, None)),
        out_specs=(q_spec, q_spec), check_vma=False))


@functools.lru_cache(maxsize=None)
def _sharded_oms_fn(mesh: Mesh, axis: str, shard_rows: int, num_rows: int,
                    dim: int, packed: bool, k: int, batch_sharded: bool,
                    fused: bool, num_bands: int, num_tiles: int):
    """Compile the shard_map OMS search for one (geometry, k, batch, tile
    budget) signature. ``num_tiles`` is bucketed host-side (power of two)
    so repeated batches with similar window spans share a compile."""
    q_spec = P("data", None) if batch_sharded else P(None, None)
    band_spec = P(None, "data") if batch_sharded else P(None, None)

    def body(q, starts, ends, refs_local):
        base = jax.lax.axis_index(axis).astype(jnp.int32) * shard_rows
        vals, gidx = _local_oms(q, refs_local, base, k, num_rows, dim,
                                packed, fused, starts, ends, num_tiles)
        vals_all = jax.lax.all_gather(vals, axis, axis=1, tiled=True)
        idx_all = jax.lax.all_gather(gidx, axis, axis=1, tiled=True)
        return _merge_topk(vals_all, idx_all, k)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(q_spec, band_spec, band_spec, P(axis, None)),
        out_specs=(q_spec, q_spec), check_vma=False))


def encode_queries(db: ShardedDatabase, queries: jax.Array) -> jax.Array:
    """Encode (Q, D) bipolar queries into the bank's storage form.

    Deterministic (bit-pack to uint32 words when the bank is packed, else
    an int8 cast) — which is what makes memoizing the result in
    :class:`~repro.serve.cache.QueryHVCache` safe: cached and cold
    encodes are bit-identical by construction.
    """
    return bitpack_bipolar(queries) if db.packed else queries.astype(jnp.int8)


def _check_k(db: ShardedDatabase, k: int) -> None:
    if k > db.num_rows:
        raise ValueError(f"k={k} > bank rows {db.num_rows}")
    if k > db.shard_rows:
        raise ValueError(
            f"k={k} exceeds shard_rows={db.shard_rows}; use fewer shards or "
            f"a smaller k (local top-k needs k candidates per shard)")


def search_database_encoded(db: ShardedDatabase, q_enc: jax.Array, k: int
                            ) -> tuple[jax.Array, jax.Array]:
    """Top-k search over *already encoded* queries (see
    :func:`encode_queries`) — the serving hot path, where encodes come
    out of the query-HV cache."""
    _check_k(db, k)

    if db.mesh is None:
        if db.emulated_shards > 1:
            vals_blocks, idx_blocks = [], []
            for s in range(db.emulated_shards):
                r_local = db.data[s * db.shard_rows:(s + 1) * db.shard_rows]
                if db.fused:
                    vals, gidx = _local_topk_fused(
                        q_enc, r_local, s * db.shard_rows, k, db.num_rows,
                        db.dim, block_q=db.block_q, block_r=db.block_r,
                        word_chunk=db.word_chunk)
                else:
                    scores = _local_scores(q_enc, r_local, dim=db.dim,
                                           packed=db.packed)
                    vals, gidx = _local_topk(scores, s * db.shard_rows, k,
                                             db.num_rows)
                vals_blocks.append(vals)
                idx_blocks.append(gidx)
            return _merge_topk(jnp.concatenate(vals_blocks, axis=1),
                               jnp.concatenate(idx_blocks, axis=1), k)
        if db.fused:
            vals, gidx = _local_topk_fused(q_enc, db.data, 0, k, db.num_rows,
                                           db.dim, block_q=db.block_q,
                                           block_r=db.block_r,
                                           word_chunk=db.word_chunk)
            return gidx, vals
        scores = _local_scores(q_enc, db.data, dim=db.dim, packed=db.packed)
        vals, gidx = _local_topk(scores, 0, k, db.num_rows)
        return gidx, vals

    data_n = db.mesh.shape.get("data", 1)
    batch_sharded = data_n > 1 and q_enc.shape[0] % data_n == 0
    fn = _sharded_search_fn(db.mesh, db.axis, db.shard_rows, db.num_rows,
                            db.dim, db.packed, k, batch_sharded, db.fused,
                            (db.block_q, db.block_r, db.word_chunk))
    return fn(q_enc, db.data)


def search_database(db: ShardedDatabase, queries: jax.Array, k: int
                    ) -> tuple[jax.Array, jax.Array]:
    """Top-k search of (Q, D) bipolar queries against a sharded bank.

    Returns (indices (Q, k), scores (Q, k)) over global bank rows,
    bit-identical to ``topk_search(queries, bank)`` on one device.
    """
    return search_database_encoded(db, encode_queries(db, queries), k)


# --------------------------------------------------------------------------
# open-modification search (OMS) routes
# --------------------------------------------------------------------------

def oms_plan(db: ShardedDatabase, query_prec: np.ndarray,
             cfg: OMSConfig | None = None) -> OMSPlan:
    """Host-side candidate plan for one query batch against an OMS bank:
    per-query per-block ``[start, len)`` ranges in the sorted layout, plus
    the static tile budget the banded kernel needs."""
    if db.oms is None:
        raise ValueError("bank was built without precursor=; OMS search "
                         "needs shard_database(..., precursor=...)")
    return plan_candidates(db.oms, np.asarray(query_prec),
                           cfg or OMSConfig(),
                           num_rows_padded=db.num_shards * db.shard_rows,
                           block_q=_OMS_BLOCK_Q)


def oms_search_encoded(db: ShardedDatabase, q_enc: jax.Array, plan: OMSPlan,
                       k: int) -> tuple[jax.Array, jax.Array]:
    """OMS top-k over already-encoded queries: every query scores only the
    bank rows inside its precursor window.

    Bit-identical — tie order and overflow slots included — to sentinel-
    masking the full score matrix over the sorted bank outside the plan's
    bands, running ``lax.top_k``, and translating the winners through
    ``db.oms.perm``: the per-shard/banded decomposition preserves the
    ascending-global-index tie-break exactly like the exact-search routes,
    and sentinel overflow slots (window narrower than k) are rewritten to
    the oracle's ascending masked rows before translation. Returned
    indices are *original* bank rows (decoys still ``< db.num_decoys``).
    """
    starts = jnp.asarray(plan.starts, jnp.int32)     # (B, Q)
    ends = starts + jnp.asarray(plan.lens, jnp.int32)
    idx, vals = _oms_search_inner(db, q_enc, plan, k)
    return _oms_finish(db, idx, vals, starts, ends)


def _oms_search_inner(db: ShardedDatabase, q_enc: jax.Array, plan: OMSPlan,
                      k: int) -> tuple[jax.Array, jax.Array]:
    """The routed banded search *before* the shared tail: returns top-k
    (sorted-layout idx, vals) with kernel overflow fillers still in place
    (sentinel-valued). Callers — :func:`oms_search_encoded` and the
    base+delta merge in :mod:`repro.serve.delta` — run overflow
    canonicalization + perm translation against *their* index."""
    if db.oms is None:
        raise ValueError("bank was built without precursor=")
    _check_k(db, k)
    starts = jnp.asarray(plan.starts, jnp.int32)     # (B, Q)
    ends = starts + jnp.asarray(plan.lens, jnp.int32)
    nt = int(plan.num_tiles)

    if db.mesh is None:
        if db.emulated_shards > 1:
            vals_blocks, idx_blocks = [], []
            for s in range(db.emulated_shards):
                r_local = db.data[s * db.shard_rows:(s + 1) * db.shard_rows]
                vals, gidx = _local_oms(
                    q_enc, r_local, s * db.shard_rows, k, db.num_rows,
                    db.dim, db.packed, db.fused, starts, ends, nt)
                vals_blocks.append(vals)
                idx_blocks.append(gidx)
            idx, vals = _merge_topk(jnp.concatenate(vals_blocks, axis=1),
                                    jnp.concatenate(idx_blocks, axis=1), k)
        else:
            vals, idx = _local_oms(q_enc, db.data, 0, k, db.num_rows,
                                   db.dim, db.packed, db.fused, starts, ends,
                                   nt)
    else:
        data_n = db.mesh.shape.get("data", 1)
        batch_sharded = data_n > 1 and q_enc.shape[0] % data_n == 0
        fn = _sharded_oms_fn(db.mesh, db.axis, db.shard_rows, db.num_rows,
                             db.dim, db.packed, k, batch_sharded, db.fused,
                             int(starts.shape[0]), nt)
        idx, vals = fn(q_enc, starts, ends, db.data)
    return idx, vals


def _oms_finish(db: ShardedDatabase, idx, vals, starts, ends):
    """Shared OMS tail: overflow slots -> the oracle's ascending masked
    rows, then translate every (now in-range) sorted row back to its
    original bank row through the device-resident ``db.oms_perm``. Run
    eagerly by the OMS routes and traced into the served batch program
    (:func:`_oms_batch_program`)."""
    from repro.kernels.topk_hamming import canonicalize_overflow_slots
    s_c = jnp.clip(starts, 0, db.num_rows)
    e_c = jnp.clip(ends, s_c, db.num_rows)
    idx = canonicalize_overflow_slots(idx, vals, s_c, e_c, db.num_rows)
    idx = jnp.take(db.oms_perm, idx, axis=0)
    return idx, vals


def oms_search(db: ShardedDatabase, queries: jax.Array,
               query_prec: np.ndarray, k: int,
               cfg: OMSConfig | None = None
               ) -> tuple[jax.Array, jax.Array, OMSPlan]:
    """Open-modification top-k search of (Q, D) bipolar queries.

    Returns (indices, scores, plan) — indices over original bank rows;
    the plan carries candidate/scanned fractions for accounting.
    """
    plan = oms_plan(db, query_prec, cfg)
    idx, vals = oms_search_encoded(db, encode_queries(db, queries), plan, k)
    return idx, vals, plan


def oms_search_with_fdr(db: ShardedDatabase, queries: jax.Array,
                        query_prec: np.ndarray, k: int, fdr: float = 0.01,
                        cfg: OMSConfig | None = None) -> "FDRSearchResult":
    """OMS search + target-decoy FDR in one call. Queries whose window is
    empty are excluded from the FDR estimate (never counted as decoy
    wins) and rejected."""
    idx, vals, plan = oms_search(db, queries, query_prec, k, cfg)
    return fdr_route(db, idx, vals, fdr=fdr,
                     valid=jnp.asarray(plan.has_candidate))


# --------------------------------------------------------------------------
# end-to-end routes: raw quantized spectra in, top-k out
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class QueryEncoder:
    """The query-side HD codebooks (Eq. 1) bundled for the e2e routes.

    Built from the *same* :class:`~repro.core.hd.encoding.HDEncoderConfig`
    the reference bank was encoded with (dim/num_features/num_levels/seed),
    so query and reference HVs live in one space. Holding the codebooks —
    rather than re-deriving them per batch — is what lets the serving loop
    accept raw (F,) quantized level vectors and encode on the device,
    staged or fused.
    """

    id_hvs: jax.Array     # (F, D) int8 bipolar ID codebook
    level_hvs: jax.Array  # (m, D) int8 bipolar level codebook

    @property
    def num_features(self) -> int:
        return int(self.id_hvs.shape[0])

    @property
    def dim(self) -> int:
        return int(self.id_hvs.shape[1])

    @property
    def num_levels(self) -> int:
        return int(self.level_hvs.shape[0])

    @classmethod
    def from_config(cls, *, dim: int, num_features: int, num_levels: int,
                    seed: int = 0) -> "QueryEncoder":
        id_hvs, level_hvs = make_codebooks(HDEncoderConfig(
            dim=dim, num_features=num_features, num_levels=num_levels,
            seed=seed))
        return cls(id_hvs=id_hvs, level_hvs=level_hvs)


def _check_levels(db: ShardedDatabase, enc: QueryEncoder, levels) -> None:
    if enc.dim != db.dim:
        raise ValueError(f"encoder dim {enc.dim} != bank dim {db.dim}")
    if levels.ndim != 2 or levels.shape[1] != enc.num_features:
        raise ValueError(
            f"levels shape {levels.shape} != (Q, {enc.num_features})")


def _local_topk_e2e(levels, enc: QueryEncoder, refs_local, base, k: int,
                    num_rows: int, dim: int, block_q: int | None = None,
                    block_r: int | None = None,
                    word_chunk: int | None = None):
    """Fully-fused per-shard twin of encode + ``_local_topk_fused``: one
    Pallas dispatch encodes the raw levels (Eq. 1), packs, and streams the
    shard's reference tiles — the query hypervector never reaches HBM.
    Same sentinel masking and base translation as the staged pair. The
    bank's block overrides apply where the parameter names coincide
    (``block_f`` always defers to the table / default)."""
    from repro.kernels.encode_search import encode_search_pallas
    shard_rows = refs_local.shape[0]
    nv = jnp.clip(jnp.asarray(num_rows - base, jnp.int32), 0, shard_rows)
    idx, vals = encode_search_pallas(levels, enc.id_hvs, enc.level_hvs,
                                     refs_local, dim=dim, k=k, num_valid=nv,
                                     block_q=block_q, block_r=block_r,
                                     word_chunk=word_chunk)
    return vals, idx + jnp.asarray(base, jnp.int32)


def _local_oms_e2e(levels, enc: QueryEncoder, refs_local, base, k: int,
                   num_rows: int, dim: int, starts, ends, num_tiles: int):
    """Fused-e2e twin of ``_local_oms_topk_fused``: one banded
    encode->search dispatch per band, then the same ascending-block local
    merge. Overflow slots keep their kernel fillers (``canonicalize=
    False``) for the caller's global canonicalization, exactly like the
    encoded-query path."""
    from repro.kernels.encode_search import encode_search_banded_pallas
    shard_rows = refs_local.shape[0]
    nv = jnp.clip(jnp.asarray(num_rows - base, jnp.int32), 0, shard_rows)
    vals_blocks, idx_blocks = [], []
    for b in range(starts.shape[0]):
        s_l = jnp.clip(starts[b] - base, 0, shard_rows).astype(jnp.int32)
        e_l = jnp.clip(ends[b] - base, s_l, shard_rows).astype(jnp.int32)
        idx, vals = encode_search_banded_pallas(
            levels, enc.id_hvs, enc.level_hvs, refs_local, s_l, e_l - s_l,
            dim=dim, k=k, num_valid=nv, num_tiles=num_tiles,
            block_q=_OMS_BLOCK_Q, canonicalize=False)
        vals_blocks.append(vals)
        idx_blocks.append(idx + jnp.asarray(base, jnp.int32))
    if len(vals_blocks) == 1:
        return vals_blocks[0], idx_blocks[0]
    idx, vals = _merge_topk(jnp.concatenate(vals_blocks, axis=1),
                            jnp.concatenate(idx_blocks, axis=1), k)
    return vals, idx


@functools.lru_cache(maxsize=None)
def _sharded_e2e_fn(mesh: Mesh, axis: str, shard_rows: int, num_rows: int,
                    dim: int, k: int, batch_sharded: bool,
                    blocks: tuple[int | None, ...] = (None, None, None)):
    """Compile the shard_map fused-e2e search for one (geometry, k, batch,
    block-override) signature. Codebooks are replicated; only the bank is
    row-sharded."""
    q_spec = P("data", None) if batch_sharded else P(None, None)
    rep = P(None, None)
    block_q, block_r, word_chunk = blocks

    def body(levels, id_hvs, level_hvs, refs_local):
        from repro.kernels.encode_search import encode_search_pallas
        base = jax.lax.axis_index(axis).astype(jnp.int32) * shard_rows
        nv = jnp.clip(num_rows - base, 0, shard_rows)
        idx, vals = encode_search_pallas(levels, id_hvs, level_hvs,
                                         refs_local, dim=dim, k=k,
                                         num_valid=nv, block_q=block_q,
                                         block_r=block_r,
                                         word_chunk=word_chunk)
        vals_all = jax.lax.all_gather(vals, axis, axis=1, tiled=True)
        idx_all = jax.lax.all_gather(idx + base, axis, axis=1, tiled=True)
        return _merge_topk(vals_all, idx_all, k)

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(q_spec, rep, rep, P(axis, None)),
        out_specs=(q_spec, q_spec), check_vma=False))


@functools.lru_cache(maxsize=None)
def _sharded_oms_e2e_fn(mesh: Mesh, axis: str, shard_rows: int,
                        num_rows: int, dim: int, k: int,
                        batch_sharded: bool, num_bands: int, num_tiles: int):
    """Compile the shard_map fused-e2e OMS search (banded twin of
    ``_sharded_e2e_fn``; same tile-budget bucketing as ``_sharded_oms_fn``)."""
    q_spec = P("data", None) if batch_sharded else P(None, None)
    band_spec = P(None, "data") if batch_sharded else P(None, None)
    rep = P(None, None)

    def body(levels, id_hvs, level_hvs, starts, ends, refs_local):
        base = jax.lax.axis_index(axis).astype(jnp.int32) * shard_rows
        enc = QueryEncoder(id_hvs=id_hvs, level_hvs=level_hvs)
        vals, gidx = _local_oms_e2e(levels, enc, refs_local, base, k,
                                    num_rows, dim, starts, ends, num_tiles)
        vals_all = jax.lax.all_gather(vals, axis, axis=1, tiled=True)
        idx_all = jax.lax.all_gather(gidx, axis, axis=1, tiled=True)
        return _merge_topk(vals_all, idx_all, k)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(q_spec, rep, rep, band_spec, band_spec, P(axis, None)),
        out_specs=(q_spec, q_spec), check_vma=False))


def search_database_levels(db: ShardedDatabase, enc: QueryEncoder,
                           levels: jax.Array, k: int, *,
                           fused_e2e: bool = False
                           ) -> tuple[jax.Array, jax.Array]:
    """Top-k search straight from raw (Q, F) quantized level vectors.

    Staged (default): Eq. 1 encode (``encode_levels_batch``) -> bank-form
    encode (``encode_queries``) -> ``search_database_encoded`` — each
    stage round-trips HBM, and the encoded rows are cacheable.

    Fused (``fused_e2e=True``): one Pallas dispatch per shard runs encode
    -> bit-pack -> streaming top-k with the query HV and score tiles held
    in VMEM throughout; only the (Q, k) winners reach HBM.

    Both paths are bit-identical — indices, scores, tie order — in every
    routed configuration (single device, emulated shards, mesh).
    """
    levels = jnp.asarray(levels, jnp.int32)
    _check_levels(db, enc, levels)
    if not fused_e2e:
        hv = encode_levels_batch(levels, enc.id_hvs, enc.level_hvs)
        return search_database_encoded(db, encode_queries(db, hv), k)
    _check_k(db, k)

    if db.mesh is None:
        if db.emulated_shards > 1:
            vals_blocks, idx_blocks = [], []
            for s in range(db.emulated_shards):
                r_local = db.data[s * db.shard_rows:(s + 1) * db.shard_rows]
                vals, gidx = _local_topk_e2e(levels, enc, r_local,
                                             s * db.shard_rows, k,
                                             db.num_rows, db.dim,
                                             block_q=db.block_q,
                                             block_r=db.block_r,
                                             word_chunk=db.word_chunk)
                vals_blocks.append(vals)
                idx_blocks.append(gidx)
            return _merge_topk(jnp.concatenate(vals_blocks, axis=1),
                               jnp.concatenate(idx_blocks, axis=1), k)
        vals, gidx = _local_topk_e2e(levels, enc, db.data, 0, k,
                                     db.num_rows, db.dim,
                                     block_q=db.block_q, block_r=db.block_r,
                                     word_chunk=db.word_chunk)
        return gidx, vals

    data_n = db.mesh.shape.get("data", 1)
    batch_sharded = data_n > 1 and levels.shape[0] % data_n == 0
    fn = _sharded_e2e_fn(db.mesh, db.axis, db.shard_rows, db.num_rows,
                         db.dim, k, batch_sharded,
                         (db.block_q, db.block_r, db.word_chunk))
    return fn(levels, enc.id_hvs, enc.level_hvs, db.data)


def oms_search_levels(db: ShardedDatabase, enc: QueryEncoder,
                      levels: jax.Array, plan: OMSPlan, k: int, *,
                      fused_e2e: bool = False
                      ) -> tuple[jax.Array, jax.Array]:
    """OMS top-k straight from raw (Q, F) level vectors (queries must be
    ordered to match ``plan`` — i.e. precursor-sorted like the bank).
    Staged vs fused exactly as :func:`search_database_levels`; both end in
    the shared overflow-canonicalize + perm-translate tail, so results are
    bit-identical to ``oms_search_encoded`` over the staged encodes."""
    levels = jnp.asarray(levels, jnp.int32)
    _check_levels(db, enc, levels)
    if db.oms is None:
        raise ValueError("bank was built without precursor=")
    if not fused_e2e:
        hv = encode_levels_batch(levels, enc.id_hvs, enc.level_hvs)
        return oms_search_encoded(db, encode_queries(db, hv), plan, k)
    _check_k(db, k)
    starts = jnp.asarray(plan.starts, jnp.int32)
    ends = starts + jnp.asarray(plan.lens, jnp.int32)
    idx, vals = _oms_e2e_inner(db, enc, levels, starts, ends,
                               int(plan.num_tiles), k)
    return _oms_finish(db, idx, vals, starts, ends)


def _oms_e2e_inner(db: ShardedDatabase, enc: QueryEncoder, levels, starts,
                   ends, nt: int, k: int) -> tuple[jax.Array, jax.Array]:
    """The routed fused-e2e banded search before the shared tail: top-k
    (sorted-layout idx, vals) with kernel overflow fillers in place, over
    a single device, emulated shards or the mesh. ``db.data`` may be a
    tracer (the served batch program)."""
    if db.mesh is None:
        if db.emulated_shards > 1:
            vals_blocks, idx_blocks = [], []
            for s in range(db.emulated_shards):
                r_local = db.data[s * db.shard_rows:(s + 1) * db.shard_rows]
                vals, gidx = _local_oms_e2e(levels, enc, r_local,
                                            s * db.shard_rows, k,
                                            db.num_rows, db.dim, starts,
                                            ends, nt)
                vals_blocks.append(vals)
                idx_blocks.append(gidx)
            idx, vals = _merge_topk(jnp.concatenate(vals_blocks, axis=1),
                                    jnp.concatenate(idx_blocks, axis=1), k)
        else:
            vals, idx = _local_oms_e2e(levels, enc, db.data, 0, k,
                                       db.num_rows, db.dim, starts, ends, nt)
    else:
        data_n = db.mesh.shape.get("data", 1)
        batch_sharded = data_n > 1 and levels.shape[0] % data_n == 0
        fn = _sharded_oms_e2e_fn(db.mesh, db.axis, db.shard_rows,
                                 db.num_rows, db.dim, k, batch_sharded,
                                 int(starts.shape[0]), nt)
        idx, vals = fn(levels, enc.id_hvs, enc.level_hvs, starts, ends,
                       db.data)
    return idx, vals


@functools.partial(jax.jit,
                   static_argnames=("geometry", "k", "num_tiles", "fdr"))
def _oms_batch_program(levels, id_hvs, level_hvs, starts, lens, data, perm,
                       inv, has_candidate, n, *, geometry: tuple, k: int,
                       num_tiles: int, fdr: float):
    """One served OMS batch as one device program: the fused-e2e banded
    search (:func:`_oms_e2e_inner`), the shared tail (:func:`_oms_finish`),
    the unsort into submit order, and the FDR decision
    (:func:`_fdr_decide`).

    levels/starts/lens/has_candidate are the bucket-padded batch in
    precursor-sorted order; ``inv`` (bucket,) unsorts it, pad rows
    mapping to themselves at the end. ``n`` (traced) is the real row
    count: rows at or past it are invalid for FDR, which then accepts
    exactly the set FDR over the first ``n`` rows accepts (an invalid row
    only repeats its predecessor's running FDR and is never accepted), so
    a new batch size compiles nothing. FDR runs in submit order, as the
    eager route does: under score ties the accepted set depends on order.
    ``geometry`` is :func:`_geometry` of the bank.

    Returns (indices, scores, is_target, accept, match) in submit order.
    """
    db = ShardedDatabase(data=data, oms_perm=perm, **dict(geometry))
    enc = QueryEncoder(id_hvs=id_hvs, level_hvs=level_hvs)
    ends = starts + lens
    idx, vals = _oms_e2e_inner(db, enc, levels, starts, ends, num_tiles, k)
    idx, vals = _oms_finish(db, idx, vals, starts, ends)
    idx, vals = idx[inv], vals[inv]
    valid = has_candidate[inv] & (jnp.arange(inv.shape[0]) < n)
    return (idx, vals) + _fdr_decide(idx, vals, db.num_decoys, fdr, valid)


def _oms_batch(db: ShardedDatabase, enc: QueryEncoder, levels, plan: OMSPlan,
               k: int, *, inv: np.ndarray, n: int, fdr: float):
    """Launch :func:`_oms_batch_program` for one served batch: ``levels``
    and ``plan`` bucket-padded in precursor-sorted order, ``inv`` the
    padded unsort permutation, ``n`` the real rows."""
    _check_levels(db, enc, levels)
    _check_k(db, k)
    return _oms_batch_program(
        levels, enc.id_hvs, enc.level_hvs, plan.starts, plan.lens, db.data,
        db.oms_perm, inv.astype(np.int32), plan.has_candidate, np.int32(n),
        geometry=_geometry(db), k=k, num_tiles=int(plan.num_tiles), fdr=fdr)


def _geometry(db: ShardedDatabase) -> tuple:
    """The bank's static fields as a hashable jit key: everything but
    the arrays (``data``, ``oms_perm``) and the host-side index."""
    return tuple((f.name, getattr(db, f.name))
                 for f in dataclasses.fields(db)
                 if f.name not in ("data", "oms", "oms_perm"))


def sharded_topk_search(queries: jax.Array, refs: jax.Array, k: int, *,
                        mesh: Mesh | None = None, axis: str = "model",
                        num_shards: int | None = None,
                        pack: bool | str = "auto",
                        fused: bool = False
                        ) -> tuple[jax.Array, jax.Array]:
    """One-shot sharded top-k (the oracle-comparable entry point).

    With ``mesh``: shard over ``axis`` via shard_map (the serving path).
    With ``num_shards`` (and no mesh): run the identical local-topk/merge
    pipeline shard-by-shard on one device — used by tier-1 tests to prove
    shard-merge correctness without a multi-device runtime.
    With neither: plain ``topk_search`` (or the fused kernel over the
    whole bank when ``fused``).
    """
    if mesh is not None:
        db = shard_database(refs, mesh=mesh, axis=axis, pack=pack,
                            fused=fused)
        return search_database(db, queries, k)
    if num_shards is None or num_shards <= 1:
        if fused:
            db = shard_database(refs, mesh=None, pack=pack, fused=True)
            return search_database(db, queries, k)
        return topk_search(queries, refs, k)
    db = shard_database(refs, mesh=None, pack=pack, emulate_shards=num_shards,
                        fused=fused)
    return search_database(db, queries, k)


# --------------------------------------------------------------------------
# FDR routing over merged results
# --------------------------------------------------------------------------

@dataclasses.dataclass
class FDRSearchResult:
    """Batch search output after target-decoy FDR filtering.

    match holds the *target-library* row (bank row minus num_decoys) for
    accepted queries, -1 otherwise.
    """

    indices: np.ndarray   # (Q, k) global bank rows
    scores: np.ndarray    # (Q, k)
    is_target: np.ndarray  # (Q,) rank-0 candidate is a target (and valid)
    accept: np.ndarray    # (Q,) passed FDR
    match: np.ndarray     # (Q,) accepted target row or -1
    valid: np.ndarray | None = None  # (Q,) had >= 1 candidate (OMS batches)


def fdr_route(db: ShardedDatabase, indices: jax.Array, scores: jax.Array,
              fdr: float = 0.01, valid: jax.Array | None = None,
              num_decoys: int | None = None) -> FDRSearchResult:
    """Target-decoy competition + FDR filter over merged top-k results.

    Only rank 0 decides the competition: because decoys precede targets in
    the bank, a score tie resolves to the decoy — the conservative
    ``best_target > best_decoy`` convention of ``run_db_search``. The FDR
    estimate is computed over the queries in this batch (the serving
    analogue of per-run filtering; callers wanting run-level FDR can
    re-filter accumulated (score, is_target) pairs).

    valid: (Q,) bool for OMS batches — False marks queries with an empty
    candidate window; they are excluded from the target/decoy counts
    (mirroring ``run_db_search``: an unmatchable query is not a decoy
    win), never accepted, and reported with ``is_target=False``.

    num_decoys: override of ``db.num_decoys`` for results whose row space
    is wider than ``db`` — the base+delta merged search
    (:mod:`repro.serve.delta`), where the decoy block spans both sides.
    """
    nd = db.num_decoys if num_decoys is None else int(num_decoys)
    is_target, accept, match = _fdr_decide(indices, scores, nd, fdr, valid)
    return FDRSearchResult(
        indices=np.asarray(indices), scores=np.asarray(scores),
        is_target=np.asarray(is_target), accept=np.asarray(accept),
        match=np.asarray(match),
        valid=None if valid is None else np.asarray(valid))


def _fdr_decide(indices, scores, num_decoys: int, fdr: float, valid):
    """:func:`fdr_route`'s arithmetic on device arrays, shared by the
    eager route and the served batch program: (is_target, accept,
    match)."""
    top_idx = indices[:, 0]
    top_val = scores[:, 0]
    is_target = top_idx >= num_decoys
    accept = fdr_filter(top_val.astype(jnp.float32), is_target, fdr=fdr,
                        valid=valid)
    if valid is not None:
        is_target = is_target & valid
    match = jnp.where(accept & is_target, top_idx - num_decoys, -1)
    return is_target, accept, match


def search_with_fdr(db: ShardedDatabase, queries: jax.Array, k: int,
                    fdr: float = 0.01) -> FDRSearchResult:
    """Sharded top-k search + FDR post-filtering in one call."""
    idx, vals = search_database(db, queries, k)
    return fdr_route(db, idx, vals, fdr=fdr)


# --------------------------------------------------------------------------
# shape-bucketed dispatch
# --------------------------------------------------------------------------

def make_buckets(max_batch_size: int, num_buckets: int = 4) -> tuple[int, ...]:
    """Geometric batch-size ladder ending at ``max_batch_size``.

    E.g. ``make_buckets(32, 4) == (4, 8, 16, 32)``. Padding ragged
    flushes up to the nearest bucket keeps the set of jit signatures
    small (at most ``num_buckets`` batch shapes per bank geometry) while
    wasting at most ~2x compute on the padded rows — instead of either
    recompiling per ragged size or always padding to the maximum.
    """
    if max_batch_size < 1:
        raise ValueError(f"max_batch_size must be >= 1, got {max_batch_size}")
    if num_buckets < 1:
        raise ValueError(f"num_buckets must be >= 1, got {num_buckets}")
    bs = [int(max_batch_size)]
    while len(bs) < num_buckets and bs[-1] > 1:
        bs.append(bs[-1] // 2)
    return tuple(sorted(set(bs)))


def bucket_for(n: int, buckets: Sequence[int]) -> int:
    """The smallest bucket >= n (buckets must be sorted ascending)."""
    for b in buckets:
        if b >= n:
            return b
    raise ValueError(f"batch of {n} exceeds largest bucket {buckets[-1]}")


# --------------------------------------------------------------------------
# serving loop
# --------------------------------------------------------------------------

@dataclasses.dataclass
class QueryResult:
    """Per-request result attached by the server."""

    indices: np.ndarray  # (k,) global bank rows
    scores: np.ndarray   # (k,)
    is_target: bool
    accept: bool
    match: int           # accepted target-library row or -1
    has_candidate: bool = True  # precursor window non-empty (OMS mode)


@dataclasses.dataclass
class BatchHandle:
    """One dispatched batch's in-flight device work (executor-internal).

    ``idx``/``vals`` are *unrealized* device arrays until ``finalize``:
    JAX's async dispatch returns immediately, so the host goes back to
    assembling the next batch while the device searches this one.
    """

    reqs: list[Request]
    tenant: str
    db: ShardedDatabase
    n: int                # real rows (the rest is bucket padding)
    idx: jax.Array
    vals: jax.Array
    valid: np.ndarray | None = None  # OMS has_candidate, submit order
    inv: np.ndarray | None = None    # OMS unsort permutation
    oms: bool = False
    num_decoys: int | None = None    # merged-row-space override (delta path)
    # the one-program OMS route: (is_target, accept, match) on the device,
    # with idx/vals already unsorted into submit order (inv is then None)
    routed: tuple[jax.Array, jax.Array, jax.Array] | None = None


@dataclasses.dataclass
class ClusterBatchHandle:
    """In-flight clustering batch (the second handle type behind the
    scheduler seam — the scheduler treats handles opaquely, so the
    clustering endpoint needed no scheduler change). ``dists`` is the
    unrealized snapshot-distance launch; the sequential assign-or-spawn
    decision runs host-side at finalize."""

    reqs: list[Request]
    tenant: str
    n: int                       # real rows (the rest is bucket padding)
    hvs: np.ndarray              # (bucket, D) int8 batch
    dists: jax.Array | None     # (bucket, >=c0) device distances, or None
    c0: int                      # clusters covered by the snapshot
    struct_version: int          # clusterer structure at dispatch


_BATCH_IDS = itertools.count()  # batch numbers, unique in the process


class SearchExecutor:
    """The production device executor behind the scheduler seam.

    Implements the three-method protocol of
    :class:`~repro.serve.scheduler.ContinuousScheduler` (``dispatch`` /
    ``poll`` / ``finalize``) on top of a :class:`DBSearchServer`'s banks,
    caches, and stats — and is the *only* place serving work touches the
    device, so flush-sync and continuous modes share one code path:

      * ``dispatch`` stamps ``t_dispatch``, assembles the bucket-padded
        host batch (through the query-HV cache on the encoded/staged
        routes), ships it with ``jax.device_put`` (async H2D), and
        launches the jitted search without blocking — with two scheduler
        slots this is classic double-buffering: slot B's host-side prep
        and transfer overlap slot A's device search;
      * ``poll`` asks the result arrays whether the computation finished
        (``Array.is_ready``; conservatively True on runtimes without it);
      * ``finalize`` blocks on the device values, unsorts OMS batches,
        routes FDR, fills per-request results, stamps ``t_done``, records
        latency stats, and drops cancelled requests. A fused-e2e OMS
        batch with no delta was unsorted and FDR-routed on the device, in
        the same program as its search, so finalize only copies it to
        the host (see ``_dispatch_oms``).

    Each call is one span of :mod:`repro.serve.trace` (``serve.dispatch``,
    ``serve.finalize``, keyed by the batch number and first request id),
    with a child span per stage: ``.plan`` (OMS), ``.assemble`` and
    ``.launch`` under dispatch; ``.wait`` (the host blocked on the
    device), ``.fdr`` and ``.results`` under finalize.

    Tests replace this class with fake executors to make scheduling
    decisions deterministic — see ``tests/test_scheduler.py``.
    """

    def __init__(self, server: "DBSearchServer"):
        self.server = server

    def dispatch(self, reqs: list[Request]) -> BatchHandle:
        srv = self.server
        t = srv._clock()
        batch = next(_BATCH_IDS)
        for r in reqs:
            r.t_dispatch = t
            r.batch = batch
        n = len(reqs)
        bucket = bucket_for(n, srv.buckets)
        srv._bucket_counts[bucket] += 1
        with trace.span("serve.dispatch", batch=batch, rid0=reqs[0].rid,
                        n=n, bucket=bucket):
            tenant = reqs[0].tenant
            if reqs[0].kind == "cluster":
                return self._dispatch_cluster(reqs, tenant, bucket)
            db, delta = srv.banks.get_with_delta(tenant)  # lazy shard-on-use
            if srv.oms is not None:
                return self._dispatch_oms(reqs, db, delta, n, bucket, tenant)
            num_decoys = None
            with trace.span("serve.dispatch.assemble"):
                if delta is not None:
                    # merged base+delta search (bit-identical to a rebuilt
                    # bank). The fused-e2e route has no encoded intermediate
                    # to hand the delta, so delta batches take the staged
                    # pipeline, which is bit-identical to the fused one.
                    from repro.serve.delta import merged_search_encoded
                    inputs = (srv._encode_batch(reqs, db, bucket, tenant),
                              srv._raw_batch(reqs, bucket))
                    search = functools.partial(merged_search_encoded, db,
                                               delta)
                    num_decoys = db.num_decoys + delta.num_decoys
                elif srv.encoder is not None and srv.fused_e2e:
                    inputs = (srv._levels_batch(reqs, bucket),)
                    search = functools.partial(
                        search_database_levels, db, srv.encoder,
                        fused_e2e=True)
                else:
                    inputs = (srv._encode_batch(reqs, db, bucket, tenant),)
                    search = functools.partial(search_database_encoded, db)
            with trace.span("serve.dispatch.launch"):
                idx, vals = search(*map(jax.device_put, inputs), srv.k)
            return BatchHandle(reqs=reqs, tenant=tenant, db=db, n=n, idx=idx,
                               vals=vals, num_decoys=num_decoys)

    def _dispatch_oms(self, reqs: list[Request], db: ShardedDatabase,
                      delta, n: int, bucket: int, tenant: str
                      ) -> BatchHandle:
        """OMS dispatch: precursor-sort the batch (nearby masses share
        kernel tiles, keeping the static tile budget small — pad rows
        inherit the highest real precursor), plan host-side, launch the
        banded search.

        The fused-e2e route with no delta launches one device program
        (:func:`_oms_batch_program`): search, OMS tail, unsort and FDR.
        No other device op runs between this launch and finalize's copy
        to the host, so the host's work on the next batch overlaps this
        batch's kernel. Every other route unsorts at finalize and routes
        FDR there. With a non-empty delta the plan and search run merged
        over base + delta (see :mod:`repro.serve.delta`) — the fused-e2e
        shortcut falls back to the staged pipeline for those batches,
        which is bit-identical."""
        srv = self.server
        num_decoys = None
        with trace.span("serve.dispatch.plan") as attrs:
            prec = np.asarray([r.precursor for r in reqs], np.float32)
            order = np.argsort(prec, kind="stable")
            inv = np.argsort(order, kind="stable")
            prec_padded = np.concatenate(
                [prec[order], np.full(bucket - n, prec[order][-1],
                                      np.float32)])
            if delta is not None:
                from repro.serve.delta import merged_oms_plan
                plan = merged_oms_plan(db, delta, prec_padded, srv.oms)
                attrs["tiles"] = int(plan.base.num_tiles)  # banded side
            else:
                plan = oms_plan(db, prec_padded, srv.oms)
                attrs["tiles"] = int(plan.num_tiles)
        single = delta is None and srv.fused_e2e
        with trace.span("serve.dispatch.assemble"):
            if single:
                inputs = (srv._levels_batch(reqs, bucket),)
                search = functools.partial(
                    _oms_batch, db, srv.encoder, fdr=srv.fdr, n=n,
                    inv=np.concatenate([inv, np.arange(n, bucket)]))
            elif delta is not None:
                from repro.serve.delta import merged_oms_search_encoded
                inputs = (srv._encode_batch(reqs, db, bucket, tenant),
                          srv._raw_batch(reqs, bucket))
                search = functools.partial(merged_oms_search_encoded, db,
                                           delta)
                num_decoys = db.num_decoys + delta.num_decoys
            else:
                inputs = (srv._encode_batch(reqs, db, bucket, tenant),)
                search = functools.partial(oms_search_encoded, db)
            inputs = [np.concatenate([b[:n][order], b[n:]]) for b in inputs]
        with trace.span("serve.dispatch.launch"):
            idx, vals, *routed = search(*map(jax.device_put, inputs), plan,
                                        srv.k)
        valid = plan.has_candidate[:n][inv]
        srv._oms_batches += 1
        srv._oms_single_launch += single
        srv._oms_cand_frac += plan.candidate_fraction
        srv._oms_scan_frac += plan.scanned_fraction
        srv._oms_no_candidate += int((~valid).sum())
        return BatchHandle(reqs=reqs, tenant=tenant, db=db, n=n, idx=idx,
                           vals=vals, valid=valid,
                           inv=None if single else inv, oms=True,
                           num_decoys=num_decoys,
                           routed=tuple(routed) if single else None)

    def _dispatch_cluster(self, reqs: list[Request], tenant: str,
                          bucket: int) -> ClusterBatchHandle:
        """Clustering dispatch: launch the batch-vs-centroids distance
        matrix (device, async) against the tenant's current snapshot;
        the assign-or-spawn loop runs at finalize."""
        srv = self.server
        cl = srv.clusterers.setdefault(
            tenant, StreamingClusterer(srv.clustering))
        hvs = np.zeros((bucket, srv.clustering.dim), np.int8)
        for i, r in enumerate(reqs):
            hvs[i] = r.query
        dists = cl.snapshot_distances(hvs)
        return ClusterBatchHandle(reqs=reqs, tenant=tenant, n=len(reqs),
                                  hvs=hvs, dists=dists, c0=cl.num_clusters,
                                  struct_version=cl.struct_version)

    def poll(self, handle) -> bool:
        arr = (handle.dists if isinstance(handle, ClusterBatchHandle)
               else handle.vals)
        if arr is None:
            return True
        return bool(getattr(arr, "is_ready", lambda: True)())

    def _finalize_cluster(self, handle: ClusterBatchHandle) -> list[Request]:
        srv = self.server
        cl = srv.clusterers[handle.tenant]
        with trace.span("serve.finalize.wait"):
            dists = (None if handle.dists is None
                     else np.asarray(handle.dists)[:handle.n])
        assigns = cl.assign_batch(handle.hvs[:handle.n], dists, handle.c0,
                                  handle.struct_version)
        t_done = srv._clock()
        live: list[Request] = []
        for r, a in zip(handle.reqs, assigns):
            if r.cancelled:
                # the spectrum still entered the cluster state (it was
                # ingested); only the response is dropped
                continue
            r.result = a
            r.t_done = t_done
            live.append(r)
        srv._cluster_requests += len(live)
        if live:
            srv.stats.record_batch(live)
            srv.tenant_stats.setdefault(
                handle.tenant, LatencyStats()).record_batch(live)
        return live

    def finalize(self, handle) -> list[Request]:
        first = handle.reqs[0]
        with trace.span("serve.finalize", batch=first.batch, rid0=first.rid):
            if isinstance(handle, ClusterBatchHandle):
                return self._finalize_cluster(handle)
            return self._finalize_search(handle)

    def _finalize_search(self, handle: BatchHandle) -> list[Request]:
        srv = self.server
        n = handle.n
        with trace.span("serve.finalize.wait"):
            # one copy to the host; blocks until the device is done
            idx, vals, *decided = (a[:n] for a in jax.device_get(
                (handle.idx, handle.vals, *(handle.routed or ()))))
        with trace.span("serve.finalize.fdr"):
            if decided:
                routed = FDRSearchResult(idx, vals, *decided,
                                         valid=handle.valid)
            else:
                if handle.inv is not None:
                    idx, vals = idx[handle.inv], vals[handle.inv]
                valid = (None if handle.valid is None
                         else jnp.asarray(handle.valid))
                routed = fdr_route(handle.db, jnp.asarray(idx),
                                   jnp.asarray(vals), fdr=srv.fdr,
                                   valid=valid, num_decoys=handle.num_decoys)
        with trace.span("serve.finalize.results"):
            t_done = srv._clock()
            live: list[Request] = []
            for i, r in enumerate(handle.reqs):
                if r.cancelled:
                    continue
                r.result = QueryResult(
                    indices=routed.indices[i], scores=routed.scores[i],
                    is_target=bool(routed.is_target[i]),
                    accept=bool(routed.accept[i]), match=int(routed.match[i]),
                    has_candidate=(True if routed.valid is None
                                   else bool(routed.valid[i])))
                r.t_done = t_done
                live.append(r)
            if live:
                srv.stats.record_batch(live)
                srv.tenant_stats.setdefault(
                    handle.tenant, LatencyStats()).record_batch(live)
        return live


class DBSearchServer:
    """Micro-batched, multi-tenant sharded DB-search server (host loop).

    Requests carry already-encoded bipolar query HVs (D,) plus a tenant
    name; each tenant searches its own bank. The server accepts either a
    single :class:`ShardedDatabase` (registered as the pinned ``default``
    tenant) or a :class:`~repro.serve.cache.BankRegistry` of per-tenant
    banks, which are sharded lazily on first use and LRU-evicted when
    cold.

    Per flush (tenant-homogeneous, per the
    :class:`~repro.serve.queue.MicroBatchQueue` policy + fairness cap):
    query rows are encoded through the content-hash
    :class:`~repro.serve.cache.QueryHVCache` (misses batch-encoded once),
    the batch is padded up to the nearest shape bucket (a bounded set of
    jit signatures shared across tenants of equal bank geometry; pad rows
    are sliced off before FDR so they never pollute the estimate), the
    sharded search runs, merged results route through per-batch FDR, and
    latency lands in both the aggregate and the per-tenant
    :class:`~repro.serve.queue.LatencyStats`.

    The cache is a pure memo of the deterministic encode, so cached and
    cold paths return bit-identical results.

    **Queue modes.** Flush-sync (default): ``step`` runs one micro-batch
    synchronously when the queue's flush policy fires — simple, but every
    request in a flush waits for the whole batch, and the *next* flush
    can't start until this one finishes. Continuous (``continuous=True``):
    a :class:`~repro.serve.scheduler.ContinuousScheduler` keeps
    ``num_slots`` batches in flight, retiring completed slots and
    admitting queued requests into freed slots every ``step`` — tail
    latency collapses because nothing waits on a flush timeout or an
    unrelated batch (``flush_timeout_s`` is inert in this mode). Both
    modes run the identical :class:`SearchExecutor` device path, so
    results are bit-identical across modes.

    **Query forms.** With ``encoder=`` (a :class:`QueryEncoder`), submits
    carry raw (F,) quantized level vectors and the server encodes on the
    device — staged (cacheable, default) or, with ``fused_e2e=True``, as
    one fused encode->pack->search kernel dispatch per shard. Without an
    encoder, submits carry pre-encoded bipolar (D,) HVs as before.

    **Live banks.** ``append`` streams new refs/decoys into a tenant's
    bank through the registry's delta path (:mod:`repro.serve.delta`) —
    searches stay exact and bit-identical to a rebuilt bank — and, with
    ``compact_threshold=``, ``step`` folds oversized deltas back into
    the packed base between batches.

    **Clustering endpoint.** With ``clustering=`` (a
    :class:`~repro.serve.clustering.ClusteringConfig`),
    ``submit_cluster`` enqueues spectra for per-tenant streaming
    assign-or-spawn clustering — a second request *kind* sharing the
    queue, fairness policy, buckets, and (continuous mode) scheduler
    slots with search; results are
    :class:`~repro.serve.clustering.ClusterAssignment` objects.
    """

    def __init__(self, db: ShardedDatabase | BankRegistry, *, k: int = 4,
                 fdr: float = 0.01, max_batch_size: int = 32,
                 flush_timeout_s: float = 0.01,
                 clock: Callable[[], float] = time.monotonic,
                 cache_bytes: int | None = 64 << 20,
                 buckets: int | Sequence[int] | None = None,
                 fairness_cap: int | None = None,
                 oms: OMSConfig | None = None,
                 encoder: QueryEncoder | None = None,
                 fused_e2e: bool = False,
                 continuous: bool = False, num_slots: int = 2,
                 executor=None,
                 compact_threshold: float | None = None,
                 clustering: ClusteringConfig | None = None):
        if isinstance(db, BankRegistry):
            self.db = None
            self.banks = db
        else:
            self.db = db
            self.banks = BankRegistry(mesh=db.mesh, axis=db.axis)
            self.banks.adopt("default", db, pin=True)
        self.k = int(k)
        self.fdr = float(fdr)
        self.max_batch_size = int(max_batch_size)
        if buckets is None:
            self.buckets: tuple[int, ...] = (self.max_batch_size,)
        elif isinstance(buckets, int):
            self.buckets = make_buckets(self.max_batch_size, buckets)
        else:
            sizes = {int(b) for b in buckets if 1 <= int(b) <= max_batch_size}
            self.buckets = tuple(sorted(sizes | {self.max_batch_size}))
        self.queue = MicroBatchQueue(max_batch_size=max_batch_size,
                                     flush_timeout_s=flush_timeout_s,
                                     clock=clock, fairness_cap=fairness_cap)
        self.query_cache = (QueryHVCache(cache_bytes) if cache_bytes
                            else None)
        self.stats = LatencyStats()
        self.tenant_stats: dict[str, LatencyStats] = {}
        self._tenant_cache: dict[str, list[int]] = {}  # tenant -> [hits, misses]
        self._bucket_counts: collections.Counter[int] = collections.Counter()
        self._clock = clock
        self.oms = oms
        self._oms_batches = 0
        self._oms_single_launch = 0
        self._oms_cand_frac = 0.0
        self._oms_scan_frac = 0.0
        self._oms_no_candidate = 0
        self.encoder = encoder
        self.fused_e2e = bool(fused_e2e)
        if self.fused_e2e and encoder is None:
            raise ValueError("fused_e2e=True requires encoder=")
        if compact_threshold is not None and not 0 < compact_threshold <= 1:
            raise ValueError(f"compact_threshold must be in (0, 1], got "
                             f"{compact_threshold}")
        self.compact_threshold = compact_threshold
        self.clustering = clustering
        self.clusterers: dict[str, StreamingClusterer] = {}
        self._cluster_requests = 0
        self.executor = SearchExecutor(self) if executor is None else executor
        self.scheduler = (ContinuousScheduler(self.queue, self.executor,
                                              num_slots=num_slots)
                          if continuous else None)

    def submit(self, query_hv, tenant: str = "default",
               precursor: float | None = None) -> int:
        """Enqueue one query for ``tenant`` (which must be registered);
        returns the request id. The query is an encoded bipolar HV (D,) —
        or, when the server was built with ``encoder=``, a raw quantized
        level vector (F,). OMS-mode servers require the query's precursor
        mass."""
        dim = self.banks.dim(tenant)  # KeyError for unknown tenants
        if self.encoder is not None:
            if self.encoder.dim != dim:
                raise ValueError(f"encoder dim {self.encoder.dim} != "
                                 f"bank dim {dim} for tenant {tenant!r}")
            q = np.asarray(query_hv, dtype=np.int32)
            if q.shape != (self.encoder.num_features,):
                raise ValueError(
                    f"query shape {q.shape} != "
                    f"({self.encoder.num_features},) levels")
        else:
            q = np.asarray(query_hv, dtype=np.int8)
            if q.shape != (dim,):
                raise ValueError(f"query shape {q.shape} != ({dim},)")
        if self.oms is not None and precursor is None:
            raise ValueError("OMS serving mode requires precursor= on submit")
        return self.queue.submit(q, tenant=tenant, precursor=precursor)

    def submit_cluster(self, query_hv, tenant: str = "default") -> int:
        """Enqueue one spectrum HV for the clustering endpoint (requires
        the server was built with ``clustering=``). Clustering tenants
        are independent of bank tenants — state is created on first use.
        The result is a :class:`~repro.serve.clustering.ClusterAssignment`."""
        if self.clustering is None:
            raise ValueError("server was built without clustering=; pass a "
                             "ClusteringConfig to serve the clustering "
                             "endpoint")
        q = np.asarray(query_hv, dtype=np.int8)
        if q.shape != (self.clustering.dim,):
            raise ValueError(
                f"query shape {q.shape} != ({self.clustering.dim},)")
        return self.queue.submit(q, tenant=tenant, kind="cluster")

    def append(self, tenant: str, refs, decoys=None, *, precursor=None,
               decoy_precursor=None) -> int:
        """Stream new refs/decoys into a tenant's bank (delegates to
        :meth:`~repro.serve.cache.BankRegistry.append`); subsequent
        searches take the exact merged base+delta path until compaction
        folds the delta in."""
        return self.banks.append(tenant, refs, decoys, precursor=precursor,
                                 decoy_precursor=decoy_precursor)

    def cancel(self, rid: int) -> bool:
        """Best-effort cancel: un-queue a pending request, or (continuous
        mode) drop an in-flight one's result at retire time."""
        if self.scheduler is not None:
            return self.scheduler.cancel(rid)
        return self.queue.cancel(rid)

    def _encode_rows(self, db: ShardedDatabase, qs: jax.Array) -> jax.Array:
        """Encode stacked raw queries into the bank's storage form: the
        deterministic bank-form cast for pre-encoded HVs, or the staged
        Eq. 1 encode first when the server carries a query encoder."""
        if self.encoder is not None:
            hv = encode_levels_batch(qs.astype(jnp.int32),
                                     self.encoder.id_hvs,
                                     self.encoder.level_hvs)
            return encode_queries(db, hv)
        return encode_queries(db, qs)

    def _raw_batch(self, reqs: list[Request], bucket: int) -> np.ndarray:
        """Stacked raw bipolar (bucket, D) int8 rows — the query form the
        *unpacked* delta side of a merged search scores against. Encoder
        servers stage the deterministic Eq. 1 encode first, so these are
        exactly the HVs the base side packs."""
        if self.encoder is not None:
            levels = self._levels_batch(reqs, bucket)
            hv = encode_levels_batch(jnp.asarray(levels, jnp.int32),
                                     self.encoder.id_hvs,
                                     self.encoder.level_hvs)
            return np.asarray(hv, np.int8)
        dim = len(reqs[0].query)
        out = np.zeros((bucket, dim), np.int8)
        for i, r in enumerate(reqs):
            out[i] = r.query
        return out

    def _levels_batch(self, reqs: list[Request], bucket: int) -> np.ndarray:
        """Assemble the raw (bucket, F) level batch for the fused-e2e
        route. Pad rows are all-zero (every peak absent) — inert under
        Eq. 1, and sliced off before FDR like any bucket padding."""
        out = np.zeros((bucket, self.encoder.num_features), np.int32)
        for i, r in enumerate(reqs):
            out[i] = r.query
        return out

    def _encode_batch(self, reqs: list[Request], db: ShardedDatabase,
                      bucket: int, tenant: str) -> np.ndarray:
        """Assemble the (bucket, width) encoded batch, through the cache.
        In e2e mode the cache memoizes *levels -> bank-form row* under a
        distinct variant tag, so the staged e2e route keeps cache reuse
        (the fused route skips the cache by design: nothing intermediate
        exists to memoize)."""
        width = db.data.shape[-1]
        out = np.zeros((bucket, width), dtype=np.dtype(db.data.dtype))
        cache = self.query_cache
        if cache is None:
            qs = jnp.asarray(np.stack([r.query for r in reqs]))
            out[: len(reqs)] = np.asarray(self._encode_rows(db, qs))
            return out
        variant = (f"{'e2e:' if self.encoder is not None else ''}"
                   f"{'packed' if db.packed else 'int8'}:{db.dim}")
        miss_pos, miss_keys = [], []
        hits = 0
        for i, r in enumerate(reqs):
            key = cache.content_key(r.query, variant=variant)
            row = cache.lookup(key)
            if row is None:
                miss_pos.append(i)
                miss_keys.append(key)
            else:
                out[i] = row
                hits += 1
        if miss_pos:
            qs = jnp.asarray(np.stack([reqs[i].query for i in miss_pos]))
            enc = np.asarray(self._encode_rows(db, qs))
            for j, i in enumerate(miss_pos):
                out[i] = enc[j]
                cache.insert(miss_keys[j], enc[j].copy())
        tc = self._tenant_cache.setdefault(tenant, [0, 0])
        tc[0] += hits
        tc[1] += len(miss_pos)
        return out

    def step(self, force: bool = False) -> list[Request]:
        """One serving-loop iteration; returns the requests completed this
        step (``result``/``t_done`` filled), [] when nothing finished.

        Flush-sync mode runs at most one micro-batch synchronously when
        the queue policy says so — or unconditionally (pending > 0) with
        ``force``, used to drain on shutdown. Continuous mode retires
        completed slots and refills them from the queue without blocking
        (``force`` waits out in-flight slots instead). Either way, due
        compactions run first — "background" compaction happens between
        batches, never under one, so no queued request is dropped (slots
        already in flight keep their pre-compaction bank handle, whose
        merged results are bit-identical anyway)."""
        self._maybe_compact()
        if self.scheduler is not None:
            return self.scheduler.step(block=force)
        if not (self.queue.ready() or (force and len(self.queue))):
            return []
        reqs = self.queue.take_batch()
        if not reqs:
            return []
        return self.executor.finalize(self.executor.dispatch(reqs))

    def _maybe_compact(self) -> int:
        """Fold every delta past ``compact_threshold`` (delta fraction)
        into its base bank; returns the number of tenants compacted."""
        if self.compact_threshold is None:
            return 0
        done = 0
        for t in self.banks.tenants_with_delta():
            if self.banks.delta_fraction(t) >= self.compact_threshold:
                if self.banks.compact(t):
                    done += 1
        return done

    def run_until_drained(self) -> list[Request]:
        """Serve until queue and in-flight slots are empty; returns all
        completed requests."""
        if self.scheduler is not None:
            return self.scheduler.drain()
        done: list[Request] = []
        while len(self.queue):
            done.extend(self.step(force=True))
        return done

    def summary(self) -> dict:
        """Aggregate latency stats plus per-tenant accounting, query-cache
        counters, bank-registry counters, and bucket usage."""
        s = self.stats.summary()
        tenants = {}
        for t, st in self.tenant_stats.items():
            d = st.summary()
            h, m = self._tenant_cache.get(t, (0, 0))
            d["cache_hits"] = h
            d["cache_misses"] = m
            d["cache_hit_rate"] = h / (h + m) if h + m else 0.0
            tenants[t] = d
        s["tenants"] = tenants
        s["banks"] = self.banks.summary()
        s["query_cache"] = (self.query_cache.summary()
                            if self.query_cache else None)
        s["buckets"] = {int(b): int(c)
                        for b, c in sorted(self._bucket_counts.items())}
        s["mode"] = "continuous" if self.scheduler is not None else "flush-sync"
        s["scheduler"] = (None if self.scheduler is None
                          else self.scheduler.summary())
        s["ingest"] = {
            "compact_threshold": self.compact_threshold,
            "appends": self.banks.appends,
            "compactions": self.banks.compactions,
            "tenants_with_delta": self.banks.tenants_with_delta(),
        }
        s["clustering"] = (None if self.clustering is None else {
            "requests": self._cluster_requests,
            "tenants": {t: c.summary()
                        for t, c in self.clusterers.items()},
        })
        s["e2e"] = (None if self.encoder is None else {
            "fused": self.fused_e2e,
            "num_features": self.encoder.num_features,
            "num_levels": self.encoder.num_levels,
        })
        if self.oms is not None:
            nb = max(self._oms_batches, 1)
            s["oms"] = {
                "tol": self.oms.tol,
                "open_tol": self.oms.open_tol,
                "open_search": self.oms.open_search,
                "batches": self._oms_batches,
                "single_launch_batches": self._oms_single_launch,
                "candidate_fraction": self._oms_cand_frac / nb,
                "scanned_fraction": self._oms_scan_frac / nb,
                "no_candidate": self._oms_no_candidate,
            }
        else:
            s["oms"] = None
        return s
