"""Public jit'd wrappers for the fused streaming top-k Hamming kernels."""

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
from repro.kernels.topk_hamming.topk_hamming import (
    topk_hamming_banded_pallas_call,
    topk_hamming_pallas_call,
)

_SENTINEL = jnp.iinfo(jnp.int32).min


def _prepare(q, r, k: int, block_q: int, block_r: int, word_chunk: int):
    """Check the operands and pad them for the kernels; returns
    ``(q, r, block_q, block_r)`` with the blocks shrunk to the problem."""
    if q.ndim != 2 or r.ndim != 2 or q.shape[1] != r.shape[1]:
        raise ValueError(f"bad operand shapes {q.shape} x {r.shape}")
    if q.dtype != r.dtype:
        raise ValueError(f"dtype mismatch {q.dtype} vs {r.dtype}")
    packed = q.dtype == jnp.uint32
    if not packed and q.dtype != jnp.int8:
        raise ValueError(f"expected uint32 (packed) or int8, got {q.dtype}")
    Q, W = q.shape
    R = r.shape[0]
    if not 1 <= k <= R:
        raise ValueError(f"k={k} must be in [1, {R}]")

    # shrink blocks to the (aligned) problem so tiny searches don't pay
    # full 128x128 tiles in interpret mode
    bq = min(block_q, round_up(Q, 8))
    br = min(block_r, round_up(R, 128))
    # bank rows are padded only up to one tile — a padded copy of a large
    # bank on every call is what the ragged last tile avoids: it reads
    # past the end, and its columns mask off
    pq, pr = (-Q) % bq, max(br - R, 0)
    pw = word_padding(W, word_chunk) if packed else (-W) % 128
    if pq or pw:
        q = jnp.pad(q, ((0, pq), (0, pw)))
    if pr or pw:
        r = jnp.pad(r, ((0, pr), (0, pw)))
    return q, r, bq, br


def topk_hamming_pallas(
    q: jax.Array,
    r: jax.Array,
    *,
    dim: int,
    k: int,
    num_valid: jax.Array | int | None = None,
    block_q: int | None = None,
    block_r: int | None = None,
    word_chunk: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused top-k search: (Q, W|D) x (R, W|D) -> (idx (Q, k), vals (Q, k)).

    uint32 inputs are bit-packed HVs scored by XOR+popcount on the bipolar
    dot-product scale (``dim - 2 * popcount``); int8 inputs score by a
    plain integer dot (the ``D % 32 != 0`` fallback). Bit-identical to
    ``lax.top_k`` over the full score matrix — tie order included — but
    the (Q, R) matrix stays in VMEM tiles and only (Q, k) reaches HBM.

    num_valid: reference rows at or past this count score as a sentinel
      below any real score (the shard-padding mask of
      ``repro.serve.db_search._local_topk``); may be a traced scalar.
      Defaults to all R rows.

    block_q/block_r/word_chunk: explicit tile sizes (validated for TPU
      alignment); ``None`` resolves through the active tuning table for
      this (device kind, shape bucket), else the 128x128 defaults — see
      :mod:`repro.kernels.block_utils`.

    Padding is harmless: bank rows past the end (up to one tile, or read
    past the end by a ragged last tile) fall outside ``num_valid``, and
    padded words XOR to zero on both sides.
    """
    cfg = resolve_blocks(
        "topk_hamming", (q.shape[0], r.shape[0], q.shape[1]),
        {"block_q": block_q, "block_r": block_r, "word_chunk": word_chunk})
    return _topk_hamming_jit(
        q, r, dim=dim, k=k, num_valid=num_valid, block_q=cfg["block_q"],
        block_r=cfg["block_r"], word_chunk=cfg["word_chunk"],
        interpret=interpret)


@partial(jax.jit, static_argnames=("dim", "k", "block_q", "block_r",
                                   "word_chunk", "interpret"))
def _topk_hamming_jit(
    q: jax.Array,
    r: jax.Array,
    *,
    dim: int,
    k: int,
    num_valid: jax.Array | int | None,
    block_q: int,
    block_r: int,
    word_chunk: int,
    interpret: bool | None,
) -> tuple[jax.Array, jax.Array]:
    if interpret is None:
        interpret = default_interpret()
    Q, R = q.shape[0], r.shape[0]
    q, r, bq, br = _prepare(q, r, k, block_q, block_r, word_chunk)

    nv = R if num_valid is None else num_valid
    nv = jnp.minimum(jnp.asarray(nv, jnp.int32).reshape(1), R)
    vals, idx = topk_hamming_pallas_call(
        q, r, nv, dim=dim, k=k, block_q=bq, block_r=br,
        word_chunk=word_chunk, interpret=interpret)
    return idx[:Q], vals[:Q]


def canonicalize_overflow_slots(idx: jax.Array, vals: jax.Array,
                                starts: jax.Array, ends: jax.Array,
                                num_rows: int | jax.Array) -> jax.Array:
    """Rewrite sentinel-valued top-k slots to the oracle's overflow indices.

    ``lax.top_k`` over a banded-masked score matrix fills slots past the
    band's width with the lowest-index *masked* columns (ties at the
    sentinel break by ascending index). The banded kernel never visits most
    masked columns, so its overflow slots carry arbitrary filler indices;
    this rewrites them to the m-th smallest row outside the bands — making
    banded results bit-identical to the masked full matrix, overflow slots
    included.

    starts/ends: (B, Q) ascending disjoint bands per query (clipped to
    ``num_rows``). Returns idx with sentinel slots canonicalized.
    """
    if starts.ndim == 1:
        starts = starts[None, :]
        ends = ends[None, :]
    sentinel = vals == _SENTINEL
    n_real = jnp.sum(~sentinel, axis=1, keepdims=True)
    k = idx.shape[1]
    m = jnp.arange(k, dtype=jnp.int32)[None, :] - n_real  # rank among masked
    # masked rows form B+1 runs: [0, s_0), [e_0, s_1), ..., [e_{B-1}, rows)
    num_bands = starts.shape[0]
    run_start = [jnp.zeros_like(starts[0])]
    run_len = []
    for b in range(num_bands):
        run_len.append(starts[b] - run_start[-1])
        run_start.append(ends[b])
    rows = jnp.asarray(num_rows, jnp.int32)
    run_len.append(rows - run_start[-1])
    col = jnp.zeros_like(m)
    cum = jnp.zeros_like(starts[0])
    done = jnp.zeros(m.shape, bool)
    for rs, rl in zip(run_start, run_len):
        in_run = ~done & (m < (cum + rl)[:, None])
        col = jnp.where(in_run, rs[:, None] + (m - cum[:, None]), col)
        done = done | in_run
        cum = cum + rl
    return jnp.where(sentinel, col, idx)


def topk_hamming_banded_pallas(
    q: jax.Array,
    r: jax.Array,
    starts: jax.Array,
    lens: jax.Array,
    *,
    dim: int,
    k: int,
    num_valid: jax.Array | int | None = None,
    num_tiles: int | None = None,
    block_q: int | None = None,
    block_r: int | None = None,
    word_chunk: int | None = None,
    interpret: bool | None = None,
    canonicalize: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Banded fused top-k: each query scores only reference rows in its own
    ``[starts[q], starts[q] + lens[q])`` band (an OMS precursor window over
    a precursor-sorted bank).

    Blocks resolve like :func:`topk_hamming_pallas` (explicit -> tuning
    table -> defaults), under the op key ``topk_hamming_banded``.

    Bit-identical to sentinel-masking the full (Q, R) score matrix outside
    the band (and at or past ``num_valid``) and running ``lax.top_k`` — tie
    order and, with ``canonicalize=True``, overflow slots included — but
    only ``num_tiles`` R tiles per Q block are fetched and scored.

    num_tiles: static per-Q-block tile budget. Every query's (clipped) band
      in a Q block must fit in ``num_tiles * block_r`` rows starting at the
      block's lowest band start — callers compute it host-side from the
      batch's windows (``repro.serve.oms.plan_candidates``). ``None`` scans
      the full bank (always correct, no work saved).
    canonicalize: rewrite sentinel overflow slots (band narrower than k) to
      the oracle's ascending masked indices. Per-shard callers that merge
      and canonicalize globally switch this off.
    """
    cfg = resolve_blocks(
        "topk_hamming_banded", (q.shape[0], r.shape[0], q.shape[1]),
        {"block_q": block_q, "block_r": block_r, "word_chunk": word_chunk})
    return _topk_hamming_banded_jit(
        q, r, starts, lens, dim=dim, k=k, num_valid=num_valid,
        num_tiles=num_tiles, block_q=cfg["block_q"], block_r=cfg["block_r"],
        word_chunk=cfg["word_chunk"], interpret=interpret,
        canonicalize=canonicalize)


@partial(jax.jit, static_argnames=("dim", "k", "num_tiles", "block_q",
                                   "block_r", "word_chunk", "interpret",
                                   "canonicalize"))
def _topk_hamming_banded_jit(
    q: jax.Array,
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
    word_chunk: int,
    interpret: bool | None,
    canonicalize: bool,
) -> tuple[jax.Array, jax.Array]:
    if interpret is None:
        interpret = default_interpret()
    Q, R = q.shape[0], r.shape[0]
    q, r, bq, br = _prepare(q, r, k, block_q, block_r, word_chunk)
    if starts.shape != (Q,) or lens.shape != (Q,):
        raise ValueError(
            f"starts/lens must be ({Q},), got {starts.shape}/{lens.shape}")
    pq = q.shape[0] - Q

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

    vals, idx = topk_hamming_banded_pallas_call(
        q, r, tb, s[:, None], e[:, None], dim=dim, k=k, num_tiles=nt,
        block_q=bq, block_r=br, word_chunk=word_chunk, interpret=interpret)
    idx, vals = idx[:Q], vals[:Q]
    if canonicalize:
        idx = canonicalize_overflow_slots(idx, vals, s[:Q], e[:Q], R)
    return idx, vals
