"""Public jit'd wrapper for the HD encoding Pallas kernel."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.block_utils import (
    default_interpret,
    resolve_blocks,
    round_up,
)
from repro.kernels.hd_encode.hd_encode import hd_encode_pallas_call


def hd_encode_pallas(
    levels: jax.Array,
    id_hvs: jax.Array,
    level_hvs: jax.Array,
    *,
    block_b: int | None = None,
    block_d: int | None = None,
    block_f: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """(B, F) levels + codebooks -> (B, D) bipolar int8 HVs.

    Pads B/F/D to block multiples. F-padding uses level 0 (absent) so padded
    features are inert; D-padding is sliced off; B-padding is sliced off.
    Blocks resolve explicit -> tuning table -> defaults
    (:mod:`repro.kernels.block_utils`).
    """
    cfg = resolve_blocks(
        "hd_encode",
        (levels.shape[0], level_hvs.shape[1], levels.shape[1]),
        {"block_b": block_b, "block_d": block_d, "block_f": block_f})
    return _hd_encode_jit(
        levels, id_hvs, level_hvs, block_b=cfg["block_b"],
        block_d=cfg["block_d"], block_f=cfg["block_f"], interpret=interpret)


@partial(jax.jit, static_argnames=("block_b", "block_d", "block_f", "interpret"))
def _hd_encode_jit(
    levels: jax.Array,
    id_hvs: jax.Array,
    level_hvs: jax.Array,
    *,
    block_b: int,
    block_d: int,
    block_f: int,
    interpret: bool | None,
) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    B, F = levels.shape
    m, D = level_hvs.shape
    block_f = min(block_f, round_up(F, 8))
    pb, pf, pd = (-B) % block_b, (-F) % block_f, (-D) % block_d
    if pb or pf:
        levels = jnp.pad(levels, ((0, pb), (0, pf)))
    if pf or pd:
        id_hvs = jnp.pad(id_hvs, ((0, pf), (0, pd)))
    if pd:
        level_hvs = jnp.pad(level_hvs, ((0, 0), (0, pd)))
    out = hd_encode_pallas_call(
        levels.astype(jnp.int32), id_hvs, level_hvs.astype(jnp.float32),
        block_b=block_b, block_d=block_d, block_f=block_f,
        interpret=interpret,
    )
    return out[:B, :D]
