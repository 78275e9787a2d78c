"""Shared block-size validation + trace-time tuned-config resolution.

Every kernel ops layer (``topk_hamming``, ``encode_search``, ``hd_encode``,
``imc_mvm``) resolves its block sizes through :func:`resolve_blocks`:

  1. an **explicit** caller argument wins — validated against the kernel's
     TPU tile-alignment constraints so a bad value raises a clear
     ``ValueError`` here instead of an opaque Mosaic lowering error three
     layers down;
  2. else the **active tuning table** (``repro.tune.table``, written by the
     ``repro.launch.tune`` sweep and selected via the ``REPRO_TUNING_TABLE``
     env var) is consulted for this (device kind, op, shape bucket);
  3. else the hand-tuned :data:`DEFAULTS` — today's 128x128-class tiles —
     apply unchanged.

Resolution happens at trace time (plain Python, before the jitted inner
call), so the chosen blocks become ordinary static arguments: a table swap
re-resolves on the next call and jit caches key on the concrete values.

Alignment rationale (see the Pallas guide's tiling table): the last block
dimension maps to the 128-wide lane axis and the second-to-last to 8
sublanes (float32/int32 tiles), so Q-like / sublane-side blocks must be
multiples of 8. R-like blocks sit on the sublane side of the bank tile
and allow 64s (the ops layers pad the array up to the block). Every block
or in-kernel slice on the lane axis must be a multiple of 128 or span the
whole axis — Mosaic refuses a lane slice it cannot prove 128-aligned. So
``block_d``, ``tile_cols`` and ``block_f`` (the feature axis of the level
block) are multiples of 128, and ``word_chunk`` (the packed uint32 word
slice of the popcount loop) is a multiple of 128 that the ops layers clamp
to the whole word axis when the bank is narrower (D <= 4096).
"""

from __future__ import annotations

import jax
import numpy as np

# per-op alignment constraints: block name -> required multiple
ALIGN: dict[str, dict[str, int]] = {
    "topk_hamming": {"block_q": 8, "block_r": 64, "word_chunk": 128},
    "topk_hamming_banded": {"block_q": 8, "block_r": 64, "word_chunk": 128},
    "encode_search": {"block_q": 8, "block_r": 64, "block_f": 128,
                      "word_chunk": 128},
    "encode_search_banded": {"block_q": 8, "block_r": 64, "block_f": 128,
                             "word_chunk": 128},
    "hd_encode": {"block_b": 8, "block_d": 128, "block_f": 128},
    "imc_mvm": {"block_q": 8, "block_r": 64, "tile_cols": 128},
}

# the pre-autotuner hand-picked blocks — the fallback when no table entry
# exists, and the baseline every sweep candidate must beat to displace
DEFAULTS: dict[str, dict[str, int]] = {
    "topk_hamming": {"block_q": 128, "block_r": 128, "word_chunk": 128},
    "topk_hamming_banded": {"block_q": 128, "block_r": 128,
                            "word_chunk": 128},
    "encode_search": {"block_q": 8, "block_r": 128, "block_f": 128,
                      "word_chunk": 128},
    "encode_search_banded": {"block_q": 8, "block_r": 128, "block_f": 128,
                             "word_chunk": 128},
    "hd_encode": {"block_b": 8, "block_d": 256, "block_f": 128},
    "imc_mvm": {"block_q": 128, "block_r": 128, "tile_cols": 128},
}


def default_interpret() -> bool:
    """Pallas interpret mode everywhere except on a TPU backend."""
    return jax.default_backend() != "tpu"


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def word_padding(n_words: int, word_chunk: int) -> int:
    """Zero words to append so the popcount loop's lane slices are legal:
    none when the whole word axis fits one chunk (the slice then spans the
    axis), else up to a ``word_chunk`` multiple."""
    return 0 if n_words <= word_chunk else (-n_words) % word_chunk


def band_tiles(s, e, *, block_q: int, block_r: int, total_tiles: int,
               num_tiles: int):
    """Per-Q-block ``(tile_base, tile_count)`` for the banded kernels.

    ``s``/``e``: (Q,) clipped band rows ``[s, e)``, Q a ``block_q``
    multiple. A block's base is its lowest start tile, clipped so the
    static ``num_tiles`` grid stays inside the bank; its count is the
    tiles from there to its highest end, at least 1 and at most
    ``num_tiles``. The kernels scan tiles ``[base, base + count)`` and
    skip the grid steps past the count. Array methods only: the kernel
    wrappers pass jax arrays, the OMS planner numpy ones, so the host
    counts the tiles the kernels visit.
    """
    tb = (s.reshape(-1, block_q).min(axis=1) // block_r).clip(
        0, total_tiles - num_tiles)
    last = -(-e.reshape(-1, block_q).max(axis=1) // block_r)
    tc = (last - tb).clip(1, num_tiles)
    return tb.astype(np.int32), tc.astype(np.int32)


def validate_block(op: str, name: str, value) -> int:
    """Return ``value`` if it satisfies ``op``'s alignment for ``name``,
    else raise a ``ValueError`` naming the constraint."""
    mult = ALIGN[op][name]
    if not isinstance(value, int) or isinstance(value, bool) \
            or value < mult or value % mult:
        raise ValueError(
            f"{op}: {name}={value!r} must be a positive multiple of {mult} "
            f"(TPU tile alignment — Mosaic cannot lower misaligned blocks)")
    return value


def block_aligned(op: str, cfg: dict) -> bool:
    """True when every entry of ``cfg`` is a valid block for ``op`` —
    the tuning-table sanitizer (invalid persisted entries are *dropped*,
    never raised, so a stale table degrades to defaults)."""
    try:
        for name, value in cfg.items():
            if name not in ALIGN[op]:
                return False
            validate_block(op, name, value)
    except (ValueError, KeyError):
        return False
    return True


def resolve_blocks(op: str, shape: tuple[int, ...],
                   overrides: dict) -> dict[str, int]:
    """Final block config for one kernel call.

    shape: the op's bucketing shape (e.g. ``(Q, R, W)``) — only used to
      pick the tuning-table bucket.
    overrides: caller kwargs, ``None`` meaning "not specified". Explicit
      values are validated here (clear error at the API boundary); table
      values were sanitized at load, and defaults are aligned by
      construction.
    """
    cfg = dict(DEFAULTS[op])
    # deferred so the kernel packages stay importable without repro.tune
    # (and without forcing a table load on cold import)
    from repro.tune.table import lookup_blocks
    tuned = lookup_blocks(op, shape)
    if tuned:
        for name, value in tuned.items():
            if name in cfg:
                cfg[name] = value
    for name, value in overrides.items():
        if value is not None:
            cfg[name] = validate_block(op, name, value)
    return cfg
