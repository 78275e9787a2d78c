"""Public jit'd wrapper for the bit-packed Hamming similarity kernel."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.block_utils import default_interpret, word_padding
from repro.kernels.hamming_pop.hamming_pop import hamming_pop_pallas_call


@partial(jax.jit, static_argnames=("dim", "block_q", "block_r", "word_chunk",
                                   "interpret"))
def hamming_pop_pallas(
    q_packed: jax.Array,
    r_packed: jax.Array,
    *,
    dim: int,
    block_q: int = 128,
    block_r: int = 128,
    word_chunk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """(Q, W) x (R, W) packed uint32 -> (Q, R) int32 hamming similarity.

    Zero-padded queries/refs XOR to zero against zero words only in the
    padded region, which is sliced off; word padding pads both sides with
    zeros (XOR -> 0 -> popcount 0) so similarities are unaffected.
    """
    if interpret is None:
        interpret = default_interpret()
    Q, W = q_packed.shape
    R = r_packed.shape[0]
    pq, pr = (-Q) % block_q, (-R) % block_r
    pw = word_padding(W, word_chunk)
    if pq or pw:
        q_packed = jnp.pad(q_packed, ((0, pq), (0, pw)))
    if pr or pw:
        r_packed = jnp.pad(r_packed, ((0, pr), (0, pw)))
    out = hamming_pop_pallas_call(
        q_packed, r_packed, dim=dim,
        block_q=block_q, block_r=block_r, word_chunk=word_chunk,
        interpret=interpret,
    )
    return out[:Q, :R]
