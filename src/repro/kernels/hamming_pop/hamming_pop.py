"""Pallas TPU kernel: bit-packed SLC Hamming similarity (beyond-paper).

The paper's SLC mode stores one bipolar dim per cell; on TPU the natural
equivalent packs 32 dims per uint32 lane and computes
``dim - popcount(q XOR r)`` with the vector unit — a 32x reduction in memory
traffic vs int8 HVs. Each program instance owns a (bq, br) output block.
:func:`xor_popcount` is the inner loop shared with the fused top-k kernels:
it walks the word axis in 128-lane chunks (Mosaic only takes lane slices it
can prove 128-aligned, or the whole axis) and the query rows in groups of
8, so the ``(8, br, chunk)`` XOR intermediate stays small in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


_SUB_Q = 8  # query rows per XOR pass (one sublane group)


def xor_popcount(q_ref, r_ref, out_ref, *, word_chunk: int) -> None:
    """Write ``popcount(q XOR r)`` of a (bq, W) x (br, W) uint32 tile pair
    into the (bq, br) int32 ``out_ref``. ``W`` must be at most
    ``word_chunk`` or a multiple of it.

    Both loops are real loops, not unrolled: Mosaic gives every unrolled
    intermediate its own VMEM, and 16 row groups of ``(8, 128, 128)``
    words would not fit the scoped limit.
    """
    bq, n_words = q_ref.shape
    br = r_ref.shape[0]
    wc = min(word_chunk, n_words)
    n_chunks = n_words // wc
    sq = min(_SUB_Q, bq)

    def rows(g, carry):
        r0 = pl.multiple_of(g * sq, sq)

        def chunk(c, acc):
            w0 = pl.multiple_of(c * wc, wc) if n_chunks > 1 else 0
            qc = q_ref[pl.ds(r0, sq), pl.ds(w0, wc)]        # (sq, wc)
            rc = r_ref[:, pl.ds(w0, wc)]                    # (br, wc)
            x = qc[:, None, :] ^ rc[None, :, :]             # (sq, br, wc)
            pc = jax.lax.population_count(x).astype(jnp.int32)
            return acc + pc.sum(axis=-1)

        acc = jnp.zeros((sq, br), jnp.int32)
        acc = (chunk(0, acc) if n_chunks == 1
               else jax.lax.fori_loop(0, n_chunks, chunk, acc))
        out_ref[pl.ds(r0, sq), :] = acc
        return carry

    jax.lax.fori_loop(0, bq // sq, rows, 0)


def _hamming_kernel(q_ref, r_ref, o_ref, *, dim: int, word_chunk: int):
    xor_popcount(q_ref, r_ref, o_ref, word_chunk=word_chunk)
    o_ref[...] = dim - o_ref[...]


def hamming_pop_pallas_call(
    q_packed: jax.Array,   # (Q, W) uint32
    r_packed: jax.Array,   # (R, W) uint32
    *,
    dim: int,
    block_q: int = 128,
    block_r: int = 128,
    word_chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    Q, W = q_packed.shape
    R = r_packed.shape[0]
    assert Q % block_q == 0 and R % block_r == 0
    assert W % min(word_chunk, W) == 0

    kernel = functools.partial(_hamming_kernel, dim=dim,
                               word_chunk=word_chunk)
    return pl.pallas_call(
        kernel,
        grid=(Q // block_q, R // block_r),
        in_specs=[
            pl.BlockSpec((block_q, W), lambda i, j: (i, 0)),
            pl.BlockSpec((block_r, W), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, block_r), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Q, R), jnp.int32),
        interpret=interpret,
    )(q_packed, r_packed)
