"""Public jit'd wrappers for the fused encode -> pack -> top-k kernels.

Padding conventions (all inert by construction, proven by
``tests/test_encode_search_fused.py``):

  * **features** pad to a ``block_f`` multiple with level 0 (absent peak)
    and zero ID rows — zero contribution to the accumulator;
  * **HD dims** pad to the bank's storage width (a ``word_chunk``-word
    multiple when packed and wider than one chunk, a 128-lane multiple
    for int8) with zero
    codebook columns: the accumulator is 0 there, so queries encode the
    pad dims to sign(0) = -1 -> packed bit 0, while padded reference
    words/columns are zero — XOR popcount and int8 dot cross terms both
    vanish, leaving scores on the true ``dim`` scale;
  * **query rows** pad with all-zero spectra and are sliced off;
  * **reference rows** pad only up to one R tile — padding a large bank
    would copy it on every call: the ragged last tile reads past the end,
    and those columns mask to the sentinel via ``num_valid``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.block_utils import (
    default_interpret,
    resolve_blocks,
    round_up,
    word_padding,
)
from repro.kernels.encode_search.encode_search import (
    encode_search_banded_pallas_call,
    encode_search_pallas_call,
)
from repro.kernels.topk_hamming.ops import canonicalize_overflow_slots


def _check_operands(levels, id_hvs, level_hvs, r, k):
    if levels.ndim != 2 or id_hvs.ndim != 2 or level_hvs.ndim != 2:
        raise ValueError(
            f"bad operand ranks {levels.shape} / {id_hvs.shape} / "
            f"{level_hvs.shape}")
    F, D = id_hvs.shape
    if levels.shape[1] != F or level_hvs.shape[1] != D:
        raise ValueError(
            f"codebook shapes disagree: levels {levels.shape}, id "
            f"{id_hvs.shape}, level {level_hvs.shape}")
    packed = r.dtype == jnp.uint32
    if packed:
        if D % 32 != 0 or r.shape[1] != D // 32:
            raise ValueError(
                f"packed bank width {r.shape[1]} != D/32 for D={D}")
    elif r.dtype == jnp.int8:
        if r.shape[1] != D:
            raise ValueError(f"bank width {r.shape[1]} != D={D}")
    else:
        raise ValueError(f"expected uint32 (packed) or int8 bank, "
                         f"got {r.dtype}")
    if not 1 <= k <= r.shape[0]:
        raise ValueError(f"k={k} must be in [1, {r.shape[0]}]")
    return packed


def _pad_operands(levels, id_hvs, level_hvs, r, *, packed: bool, bq: int,
                  br: int, block_f: int, word_chunk: int):
    """Apply the module-docstring padding; returns the padded operands."""
    Q, F = levels.shape
    D = id_hvs.shape[1]
    R, W = r.shape
    pq, pf, pr = (-Q) % bq, (-F) % block_f, max(br - R, 0)
    pw = word_padding(W, word_chunk) if packed else (-D) % 128
    pd = 32 * pw if packed else pw
    if pq or pf:
        levels = jnp.pad(levels, ((0, pq), (0, pf)))
    if pf or pd:
        id_hvs = jnp.pad(id_hvs, ((0, pf), (0, pd)))
    level_hvs = level_hvs.astype(jnp.float32)
    if pd:
        level_hvs = jnp.pad(level_hvs, ((0, 0), (0, pd)))
    if pr or pw:  # rows pad only up to one tile; see module docstring
        r = jnp.pad(r, ((0, pr), (0, pw)))
    return levels, id_hvs, level_hvs, r


def encode_search_pallas(
    levels: jax.Array,     # (Q, F) int quantized intensity levels
    id_hvs: jax.Array,     # (F, D) int8 bipolar ID codebook
    level_hvs: jax.Array,  # (m, D) int8 bipolar level codebook
    r: jax.Array,          # (R, D/32) uint32 packed or (R, D) int8 bank
    *,
    dim: int,
    k: int,
    num_valid: jax.Array | int | None = None,
    block_q: int | None = None,
    block_r: int | None = None,
    block_f: int | None = None,
    word_chunk: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused query pipeline: raw (Q, F) spectra -> (idx (Q, k), vals (Q, k)).

    Bit-identical — tie order and ``num_valid`` sentinel masking included
    — to the staged oracle
    ``encode_levels_batch -> encode_queries -> topk_hamming_pallas``
    (equivalently ``topk_search`` over the encoded HVs), but the encoded
    hypervector and the (Q, R) score matrix never leave VMEM: only the
    (Q, k) winners reach HBM. ``dim`` must be the true HD dimensionality
    (``id_hvs.shape[1]``); the bank's dtype selects the packed
    XOR+popcount or int8-dot score path. Blocks resolve explicit ->
    tuning table -> defaults (:mod:`repro.kernels.block_utils`).
    """
    cfg = resolve_blocks(
        "encode_search", (levels.shape[0], r.shape[0], levels.shape[1]),
        {"block_q": block_q, "block_r": block_r, "block_f": block_f,
         "word_chunk": word_chunk})
    return _encode_search_jit(
        levels, id_hvs, level_hvs, r, dim=dim, k=k, num_valid=num_valid,
        block_q=cfg["block_q"], block_r=cfg["block_r"],
        block_f=cfg["block_f"], word_chunk=cfg["word_chunk"],
        interpret=interpret)


@partial(jax.jit, static_argnames=("dim", "k", "block_q", "block_r",
                                   "block_f", "word_chunk", "interpret"))
def _encode_search_jit(
    levels: jax.Array,
    id_hvs: jax.Array,
    level_hvs: jax.Array,
    r: jax.Array,
    *,
    dim: int,
    k: int,
    num_valid: jax.Array | int | None,
    block_q: int,
    block_r: int,
    block_f: int,
    word_chunk: int,
    interpret: bool | None,
) -> tuple[jax.Array, jax.Array]:
    if interpret is None:
        interpret = default_interpret()
    packed = _check_operands(levels, id_hvs, level_hvs, r, k)
    Q, _ = levels.shape
    R = r.shape[0]
    bq = min(block_q, round_up(Q, 8))
    br = min(block_r, round_up(R, 128))
    bf = min(block_f, round_up(levels.shape[1], 8))
    levels, id_hvs, level_hvs, r = _pad_operands(
        levels.astype(jnp.int32), id_hvs, level_hvs, r, packed=packed,
        bq=bq, br=br, block_f=bf, word_chunk=word_chunk)

    nv = R if num_valid is None else num_valid
    nv = jnp.minimum(jnp.asarray(nv, jnp.int32).reshape(1), R)
    vals, idx = encode_search_pallas_call(
        levels, id_hvs, level_hvs, r, nv, dim=dim, k=k, block_q=bq,
        block_r=br, block_f=bf, word_chunk=word_chunk, interpret=interpret)
    return idx[:Q], vals[:Q]


def encode_search_banded_pallas(
    levels: jax.Array,
    id_hvs: jax.Array,
    level_hvs: jax.Array,
    r: jax.Array,
    starts: jax.Array,     # (Q,) per-query band start row
    lens: jax.Array,       # (Q,) per-query band length
    *,
    dim: int,
    k: int,
    num_valid: jax.Array | int | None = None,
    num_tiles: int | None = None,
    block_q: int | None = None,
    block_r: int | None = None,
    block_f: int | None = None,
    word_chunk: int | None = None,
    interpret: bool | None = None,
    canonicalize: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Banded fused encode->search: each raw spectrum scores only bank
    rows in its own ``[starts[q], starts[q] + lens[q])`` band (an OMS
    precursor window over a precursor-sorted bank), scanning only
    ``num_tiles`` R tiles per Q block. Same contract — tile budget,
    clipping, overflow canonicalization — as
    ``topk_hamming_banded_pallas``, with the encode fused in. Blocks
    resolve under the op key ``encode_search_banded``.
    """
    cfg = resolve_blocks(
        "encode_search_banded",
        (levels.shape[0], r.shape[0], levels.shape[1]),
        {"block_q": block_q, "block_r": block_r, "block_f": block_f,
         "word_chunk": word_chunk})
    return _encode_search_banded_jit(
        levels, id_hvs, level_hvs, r, starts, lens, dim=dim, k=k,
        num_valid=num_valid, num_tiles=num_tiles, block_q=cfg["block_q"],
        block_r=cfg["block_r"], block_f=cfg["block_f"],
        word_chunk=cfg["word_chunk"], interpret=interpret,
        canonicalize=canonicalize)


@partial(jax.jit, static_argnames=("dim", "k", "num_tiles", "block_q",
                                   "block_r", "block_f", "word_chunk",
                                   "interpret", "canonicalize"))
def _encode_search_banded_jit(
    levels: jax.Array,
    id_hvs: jax.Array,
    level_hvs: jax.Array,
    r: jax.Array,
    starts: jax.Array,
    lens: jax.Array,
    *,
    dim: int,
    k: int,
    num_valid: jax.Array | int | None,
    num_tiles: int | None,
    block_q: int,
    block_r: int,
    block_f: int,
    word_chunk: int,
    interpret: bool | None,
    canonicalize: bool,
) -> tuple[jax.Array, jax.Array]:
    if interpret is None:
        interpret = default_interpret()
    packed = _check_operands(levels, id_hvs, level_hvs, r, k)
    Q, _ = levels.shape
    R = r.shape[0]
    if starts.shape != (Q,) or lens.shape != (Q,):
        raise ValueError(
            f"starts/lens must be ({Q},), got {starts.shape}/{lens.shape}")
    bq = min(block_q, round_up(Q, 8))
    br = min(block_r, round_up(R, 128))
    bf = min(block_f, round_up(levels.shape[1], 8))
    pq = (-Q) % bq
    levels, id_hvs, level_hvs, r = _pad_operands(
        levels.astype(jnp.int32), id_hvs, level_hvs, r, packed=packed,
        bq=bq, br=br, block_f=bf, word_chunk=word_chunk)

    nv = R if num_valid is None else num_valid
    nv = jnp.minimum(jnp.asarray(nv, jnp.int32), R)
    s = jnp.clip(starts.astype(jnp.int32), 0, nv)
    e = jnp.clip(starts.astype(jnp.int32) + lens.astype(jnp.int32), s, nv)
    # edge-pad so padded queries inherit a real band and don't widen the
    # per-block tile span
    if pq:
        s = jnp.pad(s, (0, pq), mode="edge")
        e = jnp.pad(e, (0, pq), mode="edge")

    total_tiles = -(-R // br)
    nt = total_tiles if num_tiles is None else min(num_tiles, total_tiles)
    tb = jnp.min(s.reshape(-1, bq) // br, axis=1)
    tb = jnp.clip(tb, 0, total_tiles - nt).astype(jnp.int32)

    vals, idx = encode_search_banded_pallas_call(
        levels, id_hvs, level_hvs, r, tb, s[:, None], e[:, None], dim=dim,
        k=k, num_tiles=nt, block_q=bq, block_r=br, block_f=bf,
        word_chunk=word_chunk, interpret=interpret)
    idx, vals = idx[:Q], vals[:Q]
    if canonicalize:
        idx = canonicalize_overflow_slots(idx, vals, s[:Q], e[:Q], R)
    return idx, vals
