"""Public jit'd wrapper for the IMC MVM Pallas kernel.

Handles padding to MXU-aligned blocks, backend selection (interpret mode on
CPU), and defaulting the ADC full scale from the array config formula."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.block_utils import default_interpret, resolve_blocks
from repro.kernels.imc_mvm.imc_mvm import imc_mvm_pallas_call


def imc_mvm_pallas(
    queries: jax.Array,
    weights: jax.Array,
    *,
    full_scale: float,
    block_q: int | None = None,
    block_r: int | None = None,
    tile_cols: int | None = None,
    dac_limit: int = 3,
    adc_levels: int = 31,
    interpret: bool | None = None,
) -> jax.Array:
    """(Q, Dp) x (R, Dp) -> (Q, R) through the modeled analog IMC chain.

    Arbitrary Q/R/Dp are zero-padded to block multiples; zero tiles quantize
    to zero codes so padding does not perturb results. Blocks resolve
    explicit -> tuning table -> defaults
    (:mod:`repro.kernels.block_utils`).
    """
    cfg = resolve_blocks(
        "imc_mvm", (queries.shape[0], weights.shape[0], queries.shape[1]),
        {"block_q": block_q, "block_r": block_r, "tile_cols": tile_cols})
    return _imc_mvm_jit(
        queries, weights, full_scale=full_scale, block_q=cfg["block_q"],
        block_r=cfg["block_r"], tile_cols=cfg["tile_cols"],
        dac_limit=dac_limit, adc_levels=adc_levels, interpret=interpret)


@partial(
    jax.jit,
    static_argnames=(
        "block_q", "block_r", "tile_cols", "dac_limit", "adc_levels",
        "full_scale", "interpret",
    ),
)
def _imc_mvm_jit(
    queries: jax.Array,
    weights: jax.Array,
    *,
    full_scale: float,
    block_q: int,
    block_r: int,
    tile_cols: int,
    dac_limit: int,
    adc_levels: int,
    interpret: bool | None,
) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    q = queries.astype(jnp.float32)
    w = weights.astype(jnp.float32)
    Q, Dp = q.shape
    R = w.shape[0]
    pq, pr, pd = (-Q) % block_q, (-R) % block_r, (-Dp) % tile_cols
    if pq or pd:
        q = jnp.pad(q, ((0, pq), (0, pd)))
    if pr or pd:
        w = jnp.pad(w, ((0, pr), (0, pd)))
    out = imc_mvm_pallas_call(
        q, w,
        block_q=block_q, block_r=block_r, tile_cols=tile_cols,
        dac_limit=dac_limit, adc_levels=adc_levels, full_scale=full_scale,
        interpret=interpret,
    )
    return out[:Q, :R]
