"""Pallas TPU kernel for ID-level HD encoding (SpecPCM Eq. 1).

For a (bb, bd) output block the kernel holds in VMEM:
  * the level codebook slice   (m, bd)   float32 — small, m <= 64
  * the ID codebook slice      (F, bd)   — read block_f rows per F step
  * the level indices          (bb, F)

and accumulates  acc[b, d] += present[b,f] * LV[level[b,f], d] * ID[f, d]
over features f, then binarizes with the paper's sign convention. Grouped
by level, the sum is one MXU matmul per level:
``acc = sum_l LV[l] * ((level == l) @ ID)`` — a (bb, bf) 0/1 mask against
the (bf, bd) ID slice, in bfloat16 (exact for 0/±1) with float32
accumulation (exact for integer sums below 2**24).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_D_CHUNK = 4096  # HD dims per encode pass: bounds the (bb, dc) f32 partials


def d_chunk(d: int) -> int:
    """HD dims per encode pass for a ``d``-wide block: all of it up to
    :data:`_D_CHUNK`, else a 128-aligned divisor of ``d``."""
    if d <= _D_CHUNK:
        return d
    c = _D_CHUNK
    while d % c:
        c //= 2
    return c


def encode_acc(levels_ref, id_ref, lv_ref, *, num_levels: int, block_f: int,
               d0: int = 0, dc: int | None = None) -> jax.Array:
    """In-kernel Eq. 1 accumulator: (bb, dc) float32 sums over features.

    The shared inner loop of the standalone encode kernel and the fused
    encode->search kernel (``repro.kernels.encode_search``): for HD dims
    ``[d0, d0 + dc)`` accumulates ``present[b,f] * LV[level[b,f], d] *
    ID[f, d]`` over all of ``levels_ref``'s features, ``block_f`` at a time,
    one level matmul each (level 0 is the absent peak and adds nothing).
    ``lv_ref`` holds the level codebook as float32. Every partial sum is an
    integer below 2**24, so ``sign(acc)`` is bit-identical to the int32
    einsum oracle.
    """
    bb, num_features = levels_ref.shape
    dc = id_ref.shape[1] if dc is None else dc
    n_f = num_features // block_f

    def f_body(fb, acc):
        f0 = pl.multiple_of(fb * block_f, block_f) if n_f > 1 else 0
        lvl = levels_ref[:, pl.ds(f0, block_f)]                   # (bb, bf)
        ids = id_ref[pl.ds(f0, block_f), pl.ds(d0, dc)]           # (bf, dc)
        ids = ids.astype(jnp.float32).astype(jnp.bfloat16)

        def l_body(lev, acc):
            mask = jnp.where(lvl == lev, 1.0, 0.0).astype(jnp.bfloat16)
            part = jax.lax.dot(mask, ids, preferred_element_type=jnp.float32)
            return acc + lv_ref[pl.ds(lev, 1), pl.ds(d0, dc)] * part

        return jax.lax.fori_loop(1, num_levels, l_body, acc)

    acc = jnp.zeros((bb, dc), jnp.float32)
    return f_body(0, acc) if n_f == 1 else jax.lax.fori_loop(0, n_f, f_body,
                                                              acc)


def _hd_encode_kernel(levels_ref, id_ref, lv_ref, o_ref, *, num_levels: int,
                      block_f: int):
    acc = encode_acc(levels_ref, id_ref, lv_ref, num_levels=num_levels,
                     block_f=block_f)
    # sign in int32, then narrow: Mosaic cannot relay a bool mask out to
    # the int8 tile of a block only 8 rows tall
    o_ref[...] = jnp.where(acc > 0, 1, -1).astype(jnp.int32).astype(jnp.int8)


def hd_encode_pallas_call(
    levels: jax.Array,     # (B, F) int32
    id_hvs: jax.Array,     # (F, D) int8
    level_hvs: jax.Array,  # (m, D) float32 (bipolar values)
    *,
    block_b: int = 8,
    block_d: int = 256,
    block_f: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, F = levels.shape
    m, D = level_hvs.shape
    assert B % block_b == 0 and D % block_d == 0 and F % block_f == 0

    kernel = functools.partial(_hd_encode_kernel, num_levels=m,
                               block_f=block_f)
    return pl.pallas_call(
        kernel,
        grid=(B // block_b, D // block_d),
        in_specs=[
            pl.BlockSpec((block_b, F), lambda i, j: (i, 0)),
            pl.BlockSpec((F, block_d), lambda i, j: (0, j)),
            pl.BlockSpec((m, block_d), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_d), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, D), jnp.int8),
        interpret=interpret,
    )(levels, id_hvs, level_hvs)
