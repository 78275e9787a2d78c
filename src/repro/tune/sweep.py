"""Block-size sweeps for the Pallas kernel layer.

For each op a representative workload per shape bucket is timed under a
small grid of candidate block configs (the hand-tuned default always
included). The winner is persisted to the tuning table — but only when it
beats the default by a margin (:data:`WIN_MARGIN`): measurement noise must
never displace a known-good default, which is what keeps the bench-CI
``tuned >= 0.95 x default`` floor structurally safe.

Every candidate is passed as *explicit* block arguments, so an already-
active table cannot steer the sweep that is about to replace it. Results
are bit-identity-checked against the default config before a candidate
may win — tuning may change speed, never results (the property the
``tests/test_tune.py`` suite pins independently).

Banded variants fix ``block_r`` at 128: the OMS host-side tile budget
(``plan_candidates``) prices windows in 128-row tiles and the serve layer
aligns shard bases to it (``_OMS_ALIGN``); sweeping it would silently
change scanned fractions. All other parameters are fair game.
"""

from __future__ import annotations

import itertools
import time

from repro.kernels.block_utils import DEFAULTS
from repro.tune.microbench import measure_ceilings
from repro.tune.table import TuningTable, device_kind

WIN_MARGIN = 0.03  # a candidate must be >=3% faster to displace the default

OPS = ("topk_hamming", "topk_hamming_banded", "encode_search",
       "encode_search_banded", "hd_encode", "imc_mvm")

# candidate grids: name -> values (the default is always added as a
# candidate even when absent from the grid)
_GRIDS_QUICK: dict[str, dict[str, tuple[int, ...]]] = {
    "topk_hamming": {"block_q": (32, 128), "block_r": (128, 256),
                     "word_chunk": (128,)},
    "topk_hamming_banded": {"block_q": (8, 32), "block_r": (128,),
                            "word_chunk": (128,)},
    "encode_search": {"block_q": (8, 32), "block_r": (128, 256),
                      "block_f": (128,), "word_chunk": (128,)},
    "encode_search_banded": {"block_q": (8, 32), "block_r": (128,),
                             "block_f": (128,), "word_chunk": (128,)},
    "hd_encode": {"block_b": (8, 32), "block_d": (128, 256),
                  "block_f": (128,)},
    "imc_mvm": {"block_q": (32, 128), "block_r": (128,),
                "tile_cols": (128,)},
}

_GRIDS_FULL: dict[str, dict[str, tuple[int, ...]]] = {
    "topk_hamming": {"block_q": (8, 32, 128), "block_r": (128, 256, 512),
                     "word_chunk": (128, 256)},
    "topk_hamming_banded": {"block_q": (8, 16, 32), "block_r": (128,),
                            "word_chunk": (128, 256)},
    "encode_search": {"block_q": (8, 16, 32), "block_r": (128, 256),
                      "block_f": (128, 256), "word_chunk": (128, 256)},
    "encode_search_banded": {"block_q": (8, 16, 32), "block_r": (128,),
                             "block_f": (128, 256), "word_chunk": (128, 256)},
    "hd_encode": {"block_b": (8, 16, 32), "block_d": (128, 256, 512),
                  "block_f": (128, 256)},
    "imc_mvm": {"block_q": (8, 32, 128), "block_r": (128, 256),
                "tile_cols": (128,)},
}


def _candidates(op: str, quick: bool) -> list[dict[str, int]]:
    grid = (_GRIDS_QUICK if quick else _GRIDS_FULL)[op]
    names = list(grid)
    cands = [dict(zip(names, vals))
             for vals in itertools.product(*(grid[n] for n in names))]
    default = dict(DEFAULTS[op])
    if default not in cands:
        cands.insert(0, default)
    return cands


def _median_us(call, iters: int, warmup: int = 1) -> float:
    import jax
    for _ in range(warmup):
        jax.block_until_ready(call())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def _workload(op: str, quick: bool):
    """(shape, run) for one op: ``shape`` is the table's bucketing tuple,
    ``run(blocks)`` executes the op under explicit block overrides and
    returns the result arrays (for the bit-identity check)."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(12)
    if quick:
        q_n, r_n, dim, k = 32, 1024, 1024, 8
        feats, levels_n = 64, 16
    else:
        q_n, r_n, dim, k = 128, 8192, 2048, 16
        feats, levels_n = 256, 32

    def bip(shape):
        return rng.choice([-1, 1], size=shape).astype(np.int8)

    if op in ("topk_hamming", "topk_hamming_banded"):
        from repro.core.hd.similarity import bitpack_bipolar
        from repro.kernels.topk_hamming import (
            topk_hamming_banded_pallas,
            topk_hamming_pallas,
        )
        q = bitpack_bipolar(jnp.asarray(bip((q_n, dim))))
        r = bitpack_bipolar(jnp.asarray(bip((r_n, dim))))
        if op == "topk_hamming":
            def run(blocks):
                return topk_hamming_pallas(q, r, dim=dim, k=k, **blocks)
            return (q_n, r_n, dim // 32), run
        width = max(r_n // 4, k)
        starts = jnp.asarray(
            rng.integers(0, r_n - width, size=q_n).astype(np.int32))
        lens = jnp.full((q_n,), width, jnp.int32)
        nt = -(-width // 128) + 1

        def run(blocks):
            return topk_hamming_banded_pallas(
                q, r, starts, lens, dim=dim, k=k, num_tiles=nt, **blocks)
        return (q_n, r_n, dim // 32), run

    if op in ("encode_search", "encode_search_banded"):
        from repro.core.hd.similarity import bitpack_bipolar
        from repro.kernels.encode_search import (
            encode_search_banded_pallas,
            encode_search_pallas,
        )
        lv = jnp.asarray(
            rng.integers(0, levels_n, size=(q_n, feats)).astype(np.int32))
        id_hvs = jnp.asarray(bip((feats, dim)))
        level_hvs = jnp.asarray(bip((levels_n, dim)))
        bank = bitpack_bipolar(jnp.asarray(bip((r_n, dim))))
        if op == "encode_search":
            def run(blocks):
                return encode_search_pallas(lv, id_hvs, level_hvs, bank,
                                            dim=dim, k=k, **blocks)
            return (q_n, r_n, feats), run
        width = max(r_n // 4, k)
        starts = jnp.asarray(
            rng.integers(0, r_n - width, size=q_n).astype(np.int32))
        lens = jnp.full((q_n,), width, jnp.int32)
        nt = -(-width // 128) + 1

        def run(blocks):
            return encode_search_banded_pallas(
                lv, id_hvs, level_hvs, bank, starts, lens, dim=dim, k=k,
                num_tiles=nt, **blocks)
        return (q_n, r_n, feats), run

    if op == "hd_encode":
        from repro.kernels.hd_encode import hd_encode_pallas
        lv = jnp.asarray(
            rng.integers(0, levels_n, size=(q_n, feats)).astype(np.int32))
        id_hvs = jnp.asarray(bip((feats, dim)))
        level_hvs = jnp.asarray(bip((levels_n, dim)))

        def run(blocks):
            return hd_encode_pallas(lv, id_hvs, level_hvs, **blocks)
        return (q_n, dim, feats), run

    if op == "imc_mvm":
        from repro.kernels.imc_mvm import imc_mvm_pallas
        dp = 128 if quick else 512
        qf = jnp.asarray(rng.standard_normal((q_n, dp)).astype(np.float32))
        wf = jnp.asarray(
            rng.standard_normal((min(r_n, 512), dp)).astype(np.float32))

        def run(blocks):
            return imc_mvm_pallas(qf, wf, full_scale=float(dp), **blocks)
        return (q_n, int(wf.shape[0]), dp), run

    raise ValueError(f"unknown op {op!r}")


def _same_result(a, b) -> bool:
    import jax
    import numpy as np
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


def sweep_op(op: str, *, quick: bool = True, iters: int = 3) -> dict:
    """Time every candidate config for ``op``'s representative workload.

    Returns ``{"shape", "blocks", "us", "default_us", "candidates"}`` —
    ``blocks`` is the default config unless a candidate was both
    bit-identical to it and at least :data:`WIN_MARGIN` faster.
    """
    shape, run = _workload(op, quick)
    default = dict(DEFAULTS[op])
    oracle = run(default)
    default_us = _median_us(lambda: run(default), iters)
    best, best_us = default, default_us
    report = []
    for cand in _candidates(op, quick):
        if cand == default:
            report.append({"blocks": cand, "us": default_us})
            continue
        out = run(cand)
        if not _same_result(oracle, out):  # pragma: no cover — safety net
            report.append({"blocks": cand, "us": None,
                           "rejected": "result mismatch vs default config"})
            continue
        us = _median_us(lambda: run(cand), iters)
        report.append({"blocks": cand, "us": us})
        if us < best_us and us < default_us * (1.0 - WIN_MARGIN):
            best, best_us = cand, us
    return {"shape": shape, "blocks": best, "us": best_us,
            "default_us": default_us, "candidates": report}


def build_tuning_table(out_path=None, *, quick: bool = True,
                       ops=None, iters: int = 3,
                       skip_ceilings: bool = False) -> TuningTable:
    """Measure ceilings, sweep every op, persist the winning configs.

    The returned table's entries carry the measured ``us``/``default_us``
    pair (bench-CI derives its tuned-vs-default floor from them) and each
    op's achieved fraction of the measured bandwidth ceiling.
    """
    ceilings = {} if skip_ceilings else measure_ceilings(quick=quick)
    table = TuningTable(device_kind=device_kind(), ceilings=ceilings,
                        meta={"quick": bool(quick),
                              "win_margin": WIN_MARGIN})
    for op in (ops or OPS):
        res = sweep_op(op, quick=quick, iters=iters)
        table.set_entry(op, res["shape"], res["blocks"],
                        us=res["us"], default_us=res["default_us"])
    if out_path is not None:
        table.save(out_path)
    return table


def tuned_vs_default_ratio(table: TuningTable) -> float:
    """min over table entries of (default qps / tuned qps)^-1 — i.e. the
    worst tuned-vs-default throughput ratio, >= 1.0 when every winner is
    at least as fast as the default it displaced (entries missing timing
    info are skipped)."""
    ratios = []
    for buckets in table.ops.values():
        for entry in buckets.values():
            us, dus = entry.get("us"), entry.get("default_us")
            if us and dus:
                ratios.append(dus / us)
    return min(ratios) if ratios else 1.0
