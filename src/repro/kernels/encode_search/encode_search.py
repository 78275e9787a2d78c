"""Pallas TPU kernel: fused HD encode -> bit-pack -> streaming top-k search.

SpecPCM's end-to-end pipeline keeps a spectrum on-accelerator from
encoding (Eq. 1) through DB search (§III.C): the encoded hypervector is
written straight into the near-memory search unit, never round-tripping
main memory. This kernel is the TPU equivalent for the serving query hot
path. Per Q block the grid's second axis first takes ``F / block_f``
encode steps, then one step per R tile:

  * each **encode** step brings in one ``(block_f, D)`` slice of the ID
    codebook (so the codebook never has to fit VMEM whole) and adds that
    feature block's Eq. 1 terms to a float32 accumulator in VMEM scratch
    with the shared accumulator
    (:func:`repro.kernels.hd_encode.hd_encode.encode_acc`); the last one
    signs it and — for packed banks — bit-packs it to uint32 words, all
    inside the kernel, so the query hypervector **never reaches HBM** in
    any form;
  * every **search** step then scores one R tile against the resident
    encoded block with the fused search's tile scorer (XOR+popcount or
    int8 dot) and folds into the same running VMEM top-k
    (:func:`repro.kernels.topk_hamming.topk_hamming._select_topk`).

Only the ``(Q, k)`` result is ever written to HBM — the staged path's
intermediate ``(Q, D)`` encoded batch, its packed ``(Q, W)`` form, *and*
the ``(Q, R)`` score matrix all stay on-chip.

**Bit-identity.** The encode accumulates +-1 terms in float32 (exact far
beyond any feature count), signs with the paper's tie -> -1 convention,
and packs with the ``bitpack_bipolar`` bit order (+1 -> bit 1), so the
resident encoded block is bit-identical to
``encode_queries(db, encode_levels_batch(levels, ...))``; the scoring and
merge are the verbatim ``topk_hamming`` inner loops. Hence the whole
fusion matches the staged oracle bit-for-bit, tie order and sentinel
masking included. Padding is inert by construction: padded feature
columns carry level 0 (absent) with zero ID rows, padded HD dims
accumulate to 0 -> sign -1 -> packed bit 0, and padded reference
words/columns are zero, so cross terms vanish (see ops.py).

The banded variant mirrors ``_topk_banded_kernel``: a scalar-prefetched
per-Q-block tile base steers the R BlockSpec so only the tiles covering
each query's OMS precursor window are fetched, with per-query
``[start, end)`` bounds masking in-tile rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.hd_encode.hd_encode import d_chunk, encode_acc
from repro.kernels.topk_hamming.topk_hamming import (
    _SENTINEL,
    _select_topk,
    _tile_scores,
)


def _pack_bits(pos: jax.Array) -> jax.Array:
    """(bq, dc) bool -> (bq, dc // 32) uint32 with the ``bitpack_bipolar``
    convention (word w holds dims [32w, 32w+32), dim 32w at bit 0).

    Mosaic cannot split the lane axis into (words, 32), so the pack is two
    MXU matmuls against 0/2**i selector matrices — one per 16-bit half,
    each sum below 2**16 and so exact in float32.
    """
    dc = pos.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (dc, dc // 32), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (dc, dc // 32), 1)
    bit = row & 31
    own = (row >> 5) == col
    weight = (jnp.int32(1) << (bit & 15)).astype(jnp.float32)
    bits = jnp.where(pos, 1.0, 0.0).astype(jnp.bfloat16)
    halves = []
    for upper in (False, True):
        sel = own & ((bit >= 16) == upper)
        mat = jnp.where(sel, weight, 0.0).astype(jnp.bfloat16)
        halves.append(jax.lax.dot(bits, mat, preferred_element_type=jnp.float32
                                  ).astype(jnp.int32))
    words = halves[0] | (halves[1] << 16)
    return jax.lax.bitcast_convert_type(words, jnp.uint32)


def _encode_step(j, n_f: int, levels_ref, id_ref, lv_ref, acc_ref, qenc_ref,
                 *, num_levels: int, packed: bool) -> None:
    """Grid steps ``j < n_f`` of a Q block: add feature block ``j``'s Eq. 1
    terms to ``acc_ref``; the last one signs (tie -> -1) and, for packed
    banks, bit-packs the block into ``qenc_ref``."""
    d = id_ref.shape[1]
    dc = d_chunk(d)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(j < n_f)
    def _():
        for d0 in range(0, d, dc):
            acc_ref[:, d0:d0 + dc] += encode_acc(
                levels_ref, id_ref, lv_ref, num_levels=num_levels,
                block_f=levels_ref.shape[1], d0=d0, dc=dc)

    @pl.when(j == n_f - 1)
    def _():
        for d0 in range(0, d, dc):
            pos = acc_ref[:, d0:d0 + dc] > 0
            if packed:
                qenc_ref[:, d0 // 32:(d0 + dc) // 32] = _pack_bits(pos)
            else:
                # via int32: Mosaic cannot relay a bool mask out to the
                # int8 tile of a block only 8 rows tall
                qenc_ref[:, d0:d0 + dc] = jnp.where(pos, 1, -1).astype(
                    jnp.int32).astype(jnp.int8)


def _search_step(t, qenc_ref, r_ref, pc_ref, svals_ref, sidx_ref, keep, *,
                 dim: int, k: int, block_r: int, word_chunk: int,
                 packed: bool) -> None:
    """Score R tile ``t`` against the encoded block and fold it into the
    running top-k; ``keep(col)`` masks the tile's columns."""
    bq = qenc_ref.shape[0]
    br = r_ref.shape[0]
    scores = _tile_scores(qenc_ref, r_ref, pc_ref, dim=dim,
                          word_chunk=word_chunk, packed=packed)
    col = t * block_r + jax.lax.broadcasted_iota(jnp.int32, (bq, br), 1)
    scores = jnp.where(keep(col), scores, _SENTINEL)
    svals, sidx = _select_topk(
        jnp.concatenate([svals_ref[...], scores], axis=1),
        jnp.concatenate([sidx_ref[...], col], axis=1), k)
    svals_ref[...] = svals
    sidx_ref[...] = sidx


def _encode_search_kernel(nv_ref, levels_ref, id_ref, lv_ref, r_ref,
                          ovals_ref, oidx_ref, acc_ref, qenc_ref, svals_ref,
                          sidx_ref, pc_ref, *, dim: int, k: int,
                          block_r: int, word_chunk: int, packed: bool,
                          r_padded: int, num_levels: int, n_f: int):
    j = pl.program_id(1)
    bq = levels_ref.shape[0]

    @pl.when(j == 0)
    def _():
        svals_ref[...] = jnp.full((bq, k), _SENTINEL, jnp.int32)
        sidx_ref[...] = r_padded + jax.lax.broadcasted_iota(
            jnp.int32, (bq, k), 1)

    _encode_step(j, n_f, levels_ref, id_ref, lv_ref, acc_ref, qenc_ref,
                 num_levels=num_levels, packed=packed)

    @pl.when(j >= n_f)
    def _():
        _search_step(j - n_f, qenc_ref, r_ref, pc_ref, svals_ref, sidx_ref,
                     lambda col: col < nv_ref[0], dim=dim, k=k,
                     block_r=block_r, word_chunk=word_chunk, packed=packed)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        ovals_ref[...] = svals_ref[...]
        oidx_ref[...] = sidx_ref[...]


def _scratch(block_q: int, block_r: int, k: int, d: int, w: int,
             packed: bool) -> list:
    return [
        pltpu.VMEM((block_q, d), jnp.float32),          # Eq. 1 accumulator
        pltpu.VMEM((block_q, w), jnp.uint32 if packed else jnp.int8),
        pltpu.VMEM((block_q, k), jnp.int32),            # running top-k
        pltpu.VMEM((block_q, k), jnp.int32),
        pltpu.VMEM((block_q, block_r), jnp.int32),      # popcount tile
    ]


def encode_search_pallas_call(
    levels: jax.Array,     # (Q, F) int32 quantized intensity levels
    id_hvs: jax.Array,     # (F, D) int8 bipolar (D padded to the ref width)
    level_hvs: jax.Array,  # (m, D) float32 bipolar
    r: jax.Array,          # (R, W) uint32 packed, or (R, D) int8
    num_valid: jax.Array,  # (1,) int32: rows >= num_valid mask to SENTINEL
    *,
    dim: int,
    k: int,
    block_q: int = 8,
    block_r: int = 128,
    block_f: int = 128,
    word_chunk: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (vals (Q, k), idx (Q, k)): fused encode -> pack -> top-k.

    ``dim`` is the *true* HD dimensionality used on the score scale;
    ``id_hvs``/``level_hvs`` columns and ``r`` words/columns may be
    zero-padded past it (inert, see module docstring).
    """
    Q, F = levels.shape
    m, D = level_hvs.shape
    R, W = r.shape
    packed = r.dtype == jnp.uint32
    assert Q % block_q == 0 and F % block_f == 0
    assert (D == 32 * W) if packed else (D == W)
    assert not packed or W % min(word_chunk, W) == 0
    n_f = F // block_f
    last_f = n_f - 1
    n_r = pl.cdiv(R, block_r)  # a ragged last tile masks via num_valid

    kernel = functools.partial(
        _encode_search_kernel, dim=dim, k=k, block_r=block_r,
        word_chunk=word_chunk, packed=packed, r_padded=n_r * block_r,
        num_levels=m, n_f=n_f)
    return pl.pallas_call(
        kernel,
        grid=(Q // block_q, n_f + n_r),
        in_specs=[
            pl.BlockSpec((1,), lambda i, j: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((block_q, block_f),
                         lambda i, j: (i, jnp.minimum(j, last_f))),
            pl.BlockSpec((block_f, D),
                         lambda i, j: (jnp.minimum(j, last_f), 0)),
            pl.BlockSpec((m, D), lambda i, j: (0, 0)),
            pl.BlockSpec((block_r, W),
                         lambda i, j: (jnp.maximum(j - n_f, 0), 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
        ],
        scratch_shapes=_scratch(block_q, block_r, k, D, W, packed),
        interpret=interpret,
        name="encode_search",
    )(num_valid, levels, id_hvs, level_hvs, r)


def _encode_search_banded_kernel(tb_ref, levels_ref, id_ref, lv_ref, r_ref,
                                 starts_ref, ends_ref, ovals_ref, oidx_ref,
                                 acc_ref, qenc_ref, svals_ref, sidx_ref,
                                 pc_ref, *, dim: int, k: int, block_r: int,
                                 word_chunk: int, packed: bool,
                                 r_padded: int, num_levels: int, n_f: int):
    """Banded twin: after the encode steps only ``num_tiles`` R tiles per
    Q block are visited, starting at the scalar-prefetched ``tb_ref[i]``
    (OMS precursor windows), with per-query ``[start, end)`` row bounds —
    the same contract as ``topk_hamming._topk_banded_kernel``."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    bq = levels_ref.shape[0]

    @pl.when(j == 0)
    def _():
        svals_ref[...] = jnp.full((bq, k), _SENTINEL, jnp.int32)
        sidx_ref[...] = r_padded + jax.lax.broadcasted_iota(
            jnp.int32, (bq, k), 1)

    _encode_step(j, n_f, levels_ref, id_ref, lv_ref, acc_ref, qenc_ref,
                 num_levels=num_levels, packed=packed)

    @pl.when(j >= n_f)
    def _():
        _search_step(tb_ref[i] + j - n_f, qenc_ref, r_ref, pc_ref, svals_ref,
                     sidx_ref,
                     lambda col: (col >= starts_ref[...])
                     & (col < ends_ref[...]),
                     dim=dim, k=k, block_r=block_r, word_chunk=word_chunk,
                     packed=packed)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        ovals_ref[...] = svals_ref[...]
        oidx_ref[...] = sidx_ref[...]


def encode_search_banded_pallas_call(
    levels: jax.Array,     # (Q, F) int32 quantized intensity levels
    id_hvs: jax.Array,     # (F, D) int8 bipolar
    level_hvs: jax.Array,  # (m, D) float32 bipolar
    r: jax.Array,          # (R, W) uint32 packed, or (R, D) int8
    tile_base: jax.Array,  # (Q // block_q,) int32 first R tile per Q block
    starts: jax.Array,     # (Q, 1) int32 per-query band start row
    ends: jax.Array,       # (Q, 1) int32 per-query band end row (exclusive)
    *,
    dim: int,
    k: int,
    num_tiles: int,
    block_q: int = 8,
    block_r: int = 128,
    block_f: int = 128,
    word_chunk: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Banded fused encode->search: grid (Q blocks, F / block_f +
    num_tiles), scanning only tiles ``[tile_base[i], tile_base[i] +
    num_tiles)`` per Q block. Caller contract matches
    ``topk_hamming_banded_pallas_call``."""
    Q, F = levels.shape
    m, D = level_hvs.shape
    R, W = r.shape
    packed = r.dtype == jnp.uint32
    assert Q % block_q == 0 and F % block_f == 0
    assert (D == 32 * W) if packed else (D == W)
    assert not packed or W % min(word_chunk, W) == 0
    n_r = pl.cdiv(R, block_r)
    assert 1 <= num_tiles <= n_r
    n_f = F // block_f
    last_f = n_f - 1

    kernel = functools.partial(
        _encode_search_banded_kernel, dim=dim, k=k, block_r=block_r,
        word_chunk=word_chunk, packed=packed, r_padded=n_r * block_r,
        num_levels=m, n_f=n_f)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Q // block_q, n_f + num_tiles),
        in_specs=[
            pl.BlockSpec((block_q, block_f),
                         lambda i, j, tb: (i, jnp.minimum(j, last_f))),
            pl.BlockSpec((block_f, D),
                         lambda i, j, tb: (jnp.minimum(j, last_f), 0)),
            pl.BlockSpec((m, D), lambda i, j, tb: (0, 0)),
            pl.BlockSpec((block_r, W),
                         lambda i, j, tb: (tb[i] + jnp.maximum(j - n_f, 0),
                                           0)),
            pl.BlockSpec((block_q, 1), lambda i, j, tb: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i, j, tb: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda i, j, tb: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i, j, tb: (i, 0)),
        ],
        scratch_shapes=_scratch(block_q, block_r, k, D, W, packed),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
        ],
        interpret=interpret,
        name="encode_search_banded",
    )(tile_base, levels, id_hvs, level_hvs, r, starts, ends)
