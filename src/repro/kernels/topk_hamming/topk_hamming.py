"""Pallas TPU kernel: fused streaming top-k Hamming search (SpecPCM §III.C).

SpecPCM's DB search never materializes a full score matrix: the PCM array
emits per-row similarities and a near-memory unit keeps only the running
best matches. This kernel is the TPU equivalent of that dataflow. The
bit-packed reference bank is tiled over a ``(Q-block, R-block)`` grid with
the R dimension innermost; each tile computes XOR+popcount similarities in
VMEM (the ``hamming_pop`` inner loop) and folds them into a running
per-query top-k (values + row indices) held in VMEM scratch across the R
steps. Only the ``(Q, k)`` result ever reaches HBM — per-query traffic is
O(k) instead of the O(R) score row the unfused path writes and re-reads.

**Tie-breaking.** ``lax.top_k`` orders ties by ascending index. The merge
selects one output slot at a time as (max value, then min row index) over
the union of the scratch and the current tile. Candidate row indices are
distinct by construction — scratch holds rows from earlier (lower-index)
tiles plus out-of-range initials ``>= R_padded`` — so the selection is
well-defined and reproduces the oracle bit-exactly, sentinel-masked
padding rows included.

Two score variants share the merge: uint32 inputs take the packed
XOR+popcount path (scores on the bipolar dot-product scale,
``dim - 2 * popcount``); int8 inputs take a plain integer dot — the
fallback when ``D % 32 != 0`` and bit-packing is unavailable.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.hamming_pop.hamming_pop import xor_popcount

_SENTINEL = jnp.iinfo(jnp.int32).min
_BIG = jnp.iinfo(jnp.int32).max


def _select_topk(vals: jax.Array, idx: jax.Array, k: int
                 ) -> tuple[jax.Array, jax.Array]:
    """Top-k of (vals, idx) candidates, ordered (value desc, index asc).

    One slot per step: the max value, ties broken toward the minimum row
    index. Requires all candidate indices in a row to be distinct (true
    for scratch ∪ tile, see module docstring), so the selected entry is
    unique and can be retired from ``avail`` by its index.
    """
    avail = jnp.ones(vals.shape, dtype=jnp.bool_)
    out_v, out_i = [], []
    for _ in range(k):
        m = jnp.max(jnp.where(avail, vals, _SENTINEL), axis=1, keepdims=True)
        cand = avail & (vals == m)
        sel = jnp.min(jnp.where(cand, idx, _BIG), axis=1, keepdims=True)
        avail = avail & ~(cand & (idx == sel))
        out_v.append(m)
        out_i.append(sel)
    return jnp.concatenate(out_v, axis=1), jnp.concatenate(out_i, axis=1)


def _tile_scores(q_ref, r_ref, pc_ref, *, dim: int, word_chunk: int,
                 packed: bool) -> jax.Array:
    """(bq, br) int32 similarity tile: XOR+popcount on the bipolar dot scale
    for packed uint32 inputs (through the (bq, br) int32 VMEM scratch
    ``pc_ref``), a plain integer dot for int8."""
    if packed:
        xor_popcount(q_ref, r_ref, pc_ref, word_chunk=word_chunk)
        return dim - 2 * pc_ref[...]  # <q, r> for bipolar HVs, exactly
    return jax.lax.dot_general(
        q_ref[...], r_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)


def _topk_kernel(nv_ref, q_ref, r_ref, ovals_ref, oidx_ref,
                 svals_ref, sidx_ref, pc_ref, *, dim: int, k: int, block_r: int,
                 word_chunk: int, packed: bool, r_padded: int):
    j = pl.program_id(1)
    bq = q_ref.shape[0]
    br = r_ref.shape[0]

    # first R step of this Q block: reset the running top-k. Initial
    # entries sit at SENTINEL with distinct indices past every real or
    # padded row, so any tile column (masked ones included) beats them.
    @pl.when(j == 0)
    def _():
        svals_ref[...] = jnp.full((bq, k), _SENTINEL, jnp.int32)
        sidx_ref[...] = r_padded + jax.lax.broadcasted_iota(
            jnp.int32, (bq, k), 1)

    scores = _tile_scores(q_ref, r_ref, pc_ref, dim=dim,
                          word_chunk=word_chunk, packed=packed)

    col = j * block_r + jax.lax.broadcasted_iota(jnp.int32, (bq, br), 1)
    scores = jnp.where(col < nv_ref[0], scores, _SENTINEL)
    svals, sidx = _select_topk(
        jnp.concatenate([svals_ref[...], scores], axis=1),
        jnp.concatenate([sidx_ref[...], col], axis=1), k)
    svals_ref[...] = svals
    sidx_ref[...] = sidx

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        ovals_ref[...] = svals
        oidx_ref[...] = sidx


def topk_hamming_pallas_call(
    q: jax.Array,          # (Q, W) uint32 packed, or (Q, D) int8
    r: jax.Array,          # (R, W) uint32 packed, or (R, D) int8
    num_valid: jax.Array,  # (1,) int32: rows >= num_valid mask to SENTINEL
    *,
    dim: int,
    k: int,
    block_q: int = 128,
    block_r: int = 128,
    word_chunk: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (vals (Q, k), idx (Q, k)) — the streaming top-k, never
    materializing the (Q, R) score matrix."""
    Q, W = q.shape
    R = r.shape[0]
    packed = q.dtype == jnp.uint32
    assert Q % block_q == 0
    assert not packed or W % min(word_chunk, W) == 0
    n_r = pl.cdiv(R, block_r)  # a ragged last tile masks via num_valid

    kernel = functools.partial(
        _topk_kernel, dim=dim, k=k, block_r=block_r, word_chunk=word_chunk,
        packed=packed, r_padded=n_r * block_r)
    return pl.pallas_call(
        kernel,
        grid=(Q // block_q, n_r),
        in_specs=[
            pl.BlockSpec((1,), lambda i, j: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((block_q, W), lambda i, j: (i, 0)),
            pl.BlockSpec((block_r, W), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, k), jnp.int32),
            pltpu.VMEM((block_q, k), jnp.int32),
            pltpu.VMEM((block_q, block_r), jnp.int32),
        ],
        interpret=interpret,
        name="topk_hamming",
    )(num_valid, q, r)


def _topk_banded_kernel(tb_ref, q_ref, r_ref, starts_ref, ends_ref,
                        ovals_ref, oidx_ref, svals_ref, sidx_ref, pc_ref, *,
                        dim: int, k: int, block_r: int, word_chunk: int,
                        packed: bool, r_padded: int):
    """Banded variant: only ``num_tiles`` R tiles per Q block are visited,
    starting at the scalar-prefetched ``tb_ref[i]`` (OMS precursor windows).

    ``tb_ref`` generalizes the full kernel's traced ``num_valid`` scalar:
    instead of one mask bound for the whole grid, each Q block gets a tile
    base from SMEM (it steers the R BlockSpec index_map, so out-of-window
    tiles are never even fetched) and each query row gets its own
    ``[start, end)`` bounds. Columns outside the band mask to the sentinel
    exactly like ``num_valid`` padding — the merge is unchanged, so the
    result is bit-identical to masking the full score matrix.
    """
    i = pl.program_id(0)
    j = pl.program_id(1)
    bq = q_ref.shape[0]
    br = r_ref.shape[0]

    @pl.when(j == 0)
    def _():
        svals_ref[...] = jnp.full((bq, k), _SENTINEL, jnp.int32)
        sidx_ref[...] = r_padded + jax.lax.broadcasted_iota(
            jnp.int32, (bq, k), 1)

    scores = _tile_scores(q_ref, r_ref, pc_ref, dim=dim,
                          word_chunk=word_chunk, packed=packed)

    tile = tb_ref[i] + j
    col = tile * block_r + jax.lax.broadcasted_iota(jnp.int32, (bq, br), 1)
    in_band = (col >= starts_ref[...]) & (col < ends_ref[...])
    scores = jnp.where(in_band, scores, _SENTINEL)
    svals, sidx = _select_topk(
        jnp.concatenate([svals_ref[...], scores], axis=1),
        jnp.concatenate([sidx_ref[...], col], axis=1), k)
    svals_ref[...] = svals
    sidx_ref[...] = sidx

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        ovals_ref[...] = svals
        oidx_ref[...] = sidx


def topk_hamming_banded_pallas_call(
    q: jax.Array,          # (Q, W) uint32 packed, or (Q, D) int8
    r: jax.Array,          # (R, W) uint32 packed, or (R, D) int8
    tile_base: jax.Array,  # (Q // block_q,) int32 first R tile per Q block
    starts: jax.Array,     # (Q, 1) int32 per-query band start row
    ends: jax.Array,       # (Q, 1) int32 per-query band end row (exclusive)
    *,
    dim: int,
    k: int,
    num_tiles: int,
    block_q: int = 128,
    block_r: int = 128,
    word_chunk: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Banded streaming top-k: grid (Q blocks, num_tiles), scanning only
    tiles ``[tile_base[i], tile_base[i] + num_tiles)`` per Q block.

    Caller contract: for every Q block i, every query's ``[start, end)``
    must lie inside the scanned rows
    ``[tile_base[i] * block_r, (tile_base[i] + num_tiles) * block_r)``
    and ``tile_base[i] + num_tiles <= cdiv(R, block_r)`` — band rows outside
    the scanned window would be silently skipped.
    """
    Q, W = q.shape
    R = r.shape[0]
    packed = q.dtype == jnp.uint32
    assert Q % block_q == 0
    assert not packed or W % min(word_chunk, W) == 0
    n_r = pl.cdiv(R, block_r)
    assert 1 <= num_tiles <= n_r

    kernel = functools.partial(
        _topk_banded_kernel, dim=dim, k=k, block_r=block_r,
        word_chunk=word_chunk, packed=packed, r_padded=n_r * block_r)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Q // block_q, num_tiles),
        in_specs=[
            pl.BlockSpec((block_q, W), lambda i, j, tb: (i, 0)),
            pl.BlockSpec((block_r, W), lambda i, j, tb: (tb[i] + j, 0)),
            pl.BlockSpec((block_q, 1), lambda i, j, tb: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i, j, tb: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda i, j, tb: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i, j, tb: (i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, k), jnp.int32),
            pltpu.VMEM((block_q, k), jnp.int32),
            pltpu.VMEM((block_q, block_r), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
        ],
        interpret=interpret,
        name="topk_hamming_banded",
    )(tile_base, q, r, starts, ends)
