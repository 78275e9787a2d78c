"""ID-level hyperdimensional encoding (SpecPCM Eq. 1).

Each spectrum is a fixed-length feature vector (binned intensities). Encoding:

    HV = sign( sum_i  LV[level_i] * ID_i )

where ``ID_i`` is a random bipolar hypervector unique to feature position i
and ``LV[l]`` is the level hypervector for quantized intensity level l.
Level HVs are built by progressive bit-flipping so that nearby levels are
similar (standard ID-level construction used by HyperSpec/HyperOMS).

Everything is pure JAX so it jits, vmaps, and shards.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.hd.similarity import bitpack_bipolar


@dataclasses.dataclass(frozen=True)
class HDEncoderConfig:
    """Configuration for the ID-level HD encoder.

    Attributes:
      dim: HD dimensionality D (paper: 2048 clustering / 8192 DB search).
      num_features: number of m/z bins per spectrum (feature positions).
      num_levels: number of quantization levels m for intensities.
      seed: PRNG seed for codebook generation.
    """

    dim: int = 2048
    num_features: int = 1024
    num_levels: int = 32
    seed: int = 0

    def __post_init__(self):
        if self.dim <= 0 or self.num_features <= 0 or self.num_levels < 2:
            raise ValueError(f"invalid HDEncoderConfig: {self}")


def make_codebooks(cfg: HDEncoderConfig) -> tuple[jax.Array, jax.Array]:
    """Build (id_hvs, level_hvs).

    id_hvs:    (num_features, dim) bipolar int8, i.i.d. random.
    level_hvs: (num_levels, dim) bipolar int8. LV_0 is random; LV_{k+1} flips
      a fixed block of dim/(num_levels-1) positions of LV_k so that
      sim(LV_a, LV_b) decays linearly with |a-b| and LV_0 ⟂ LV_{m-1}.
    """
    key = jax.random.PRNGKey(cfg.seed)
    k_id, k_lv, k_perm = jax.random.split(key, 3)
    id_hvs = jax.random.rademacher(k_id, (cfg.num_features, cfg.dim), dtype=jnp.int8)

    base = jax.random.rademacher(k_lv, (cfg.dim,), dtype=jnp.int8)
    # Positions are flipped in a random order; level k flips the first
    # floor(k * (dim/2) / (m-1)) positions of the shuffled index set, so
    # LV_0 and LV_{m-1} differ in dim/2 positions (orthogonal, not
    # anti-correlated) and similarity decays linearly with level distance.
    perm = jax.random.permutation(k_perm, cfg.dim)
    thresholds = (
        jnp.arange(cfg.num_levels, dtype=jnp.int32)
        * (cfg.dim // 2)
        // (cfg.num_levels - 1)
    )
    # rank[j] = position of dim-index j in the flip order
    rank = jnp.zeros((cfg.dim,), jnp.int32).at[perm].set(jnp.arange(cfg.dim, dtype=jnp.int32))
    flip = rank[None, :] < thresholds[:, None]  # (m, dim) bool
    level_hvs = jnp.where(flip, -base[None, :], base[None, :]).astype(jnp.int8)
    return id_hvs, level_hvs


def quantize_levels(values: jax.Array, num_levels: int) -> jax.Array:
    """Quantize feature values in [0, 1] to integer levels [0, m-1].

    Level 0 means *absent* (zero-intensity bin): spectra are sparse peak
    lists, and only present peaks contribute to the encoding — empty bins
    shared by all spectra would otherwise add a large correlated baseline to
    every pairwise similarity. Present peaks map to levels 1..m-1.
    """
    v = jnp.clip(values, 0.0, 1.0)
    present = v > 1e-6
    lvl = 1 + jnp.minimum((v * (num_levels - 1)).astype(jnp.int32), num_levels - 2)
    return jnp.where(present, lvl, 0)


@jax.jit
def encode_levels_batch(
    levels: jax.Array,
    id_hvs: jax.Array,
    level_hvs: jax.Array,
) -> jax.Array:
    """Eq. 1 from *already quantized* levels. levels: (B, F) int in [0, m).

    Level 0 is the absent-peak sentinel and contributes nothing; sign ties
    (acc == 0) resolve to -1. Grouped by level, the sum is one
    ``(B, F) @ (F, D)`` matmul per level,
    ``acc = sum_l LV[l] * ((levels == l) @ ID)``, with bfloat16 operands
    (0 and ±1 are exact) and float32 accumulation (exact for integer sums
    below 2**24) — and its working set is ``(B, D)`` where the gather form
    of :func:`encode_batch_reference` needs ``(B, F, D)``. This is the
    levels-in entry point of the serving raw-spectrum path
    (``repro.serve.db_search.search_database_levels``) and of the bank
    encoder :func:`encode_bitpacked`, and the oracle the fused
    encode->search kernel (``repro.kernels.encode_search``) must match
    bit-exactly. Returns bipolar (B, D) int8 hypervectors.
    """
    ids = id_hvs.astype(jnp.bfloat16)
    lvs = level_hvs.astype(jnp.float32)

    def add_level(lev, acc):
        mask = (levels == lev).astype(jnp.bfloat16)
        part = jax.lax.dot(mask, ids, preferred_element_type=jnp.float32)
        return acc + part * lvs[lev]

    acc = jax.lax.fori_loop(
        1, level_hvs.shape[0], add_level,
        jnp.zeros((levels.shape[0], id_hvs.shape[1]), jnp.float32))
    # sign with the paper's convention: positive -> +1, zero or negative -> -1
    return jnp.where(acc > 0, jnp.int8(1), jnp.int8(-1))


def encode_batch_reference(
    features: jax.Array,
    id_hvs: jax.Array,
    level_hvs: jax.Array,
) -> jax.Array:
    """Pure-jnp oracle for Eq. 1. features: (B, F) float in [0,1].

    Gathers every feature's level HV — the (B, F, D) form of the equation
    — so it is for small batches only. Returns bipolar (B, D) int8
    hypervectors.
    """
    levels = quantize_levels(features, level_hvs.shape[0])  # (B, F)
    lv = level_hvs[levels]  # (B, F, D) int8
    present = (levels > 0).astype(jnp.int32)  # level 0 = absent peak
    acc = jnp.einsum(
        "bf,bfd,fd->bd",
        present,
        lv.astype(jnp.int32),
        id_hvs.astype(jnp.int32),
        preferred_element_type=jnp.int32,
    )
    return jnp.where(acc > 0, jnp.int8(1), jnp.int8(-1))


@jax.jit
def encode_batch(
    features: jax.Array,
    id_hvs: jax.Array,
    level_hvs: jax.Array,
) -> jax.Array:
    """Memory-bounded ID-level encoder: quantize, then
    :func:`encode_levels_batch` — the math of :func:`encode_batch_reference`
    without its (B, F, D) intermediate."""
    levels = quantize_levels(features, level_hvs.shape[0])
    return encode_levels_batch(levels, id_hvs, level_hvs)


# rows per bank-encoder chunk: the (chunk, D) float32 accumulator is 256 MiB
# at D=8192
_CHUNK_ROWS = 8192


@partial(jax.jit, static_argnames=("rows",))
def _encode_pack_rows(levels, start, id_hvs, level_hvs, *, rows: int):
    blk = jax.lax.dynamic_slice_in_dim(levels, start, rows)
    return bitpack_bipolar(encode_levels_batch(blk, id_hvs, level_hvs))


def encode_bitpacked(
    levels: jax.Array,
    id_hvs: jax.Array,
    level_hvs: jax.Array,
) -> jax.Array:
    """(N, F) levels -> (N, D/32) uint32 bit-packed Eq. 1 hypervectors.

    Encodes ``_CHUNK_ROWS`` rows at a time with one compiled program (the
    chunk start is traced; a ragged last chunk is taken from the end and
    trimmed): the unpacked (chunk, D) HVs are the largest intermediate, so
    a library of any size encodes in bounded memory and only packed words
    accumulate. Bit-identical to ``bitpack_bipolar(encode_batch_reference
    (...))`` row for row.
    """
    n = int(levels.shape[0])
    chunk = max(1, min(_CHUNK_ROWS, n))
    starts = list(range(0, n, chunk))
    tail = n - starts[-1]
    starts[-1] = n - chunk
    out = [_encode_pack_rows(levels, s, id_hvs, level_hvs, rows=chunk)
           for s in starts]
    if tail < chunk:
        out[-1] = out[-1][chunk - tail:]
    return out[0] if len(out) == 1 else jnp.concatenate(out)
