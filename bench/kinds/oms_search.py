"""The ``oms_search`` deployment kind: one spectral library served for
open-modification search (``bench/configs/hek293.json``,
``iprg2012.json``).

Set-up makes the library and the query pools on the device from the seed
(``gen.py``), builds the bank and the server (``adapter.py``) and warms
every OMS tile budget the mix reaches (``loadgen.warm_batches``). The
window's counters are the OMS planner's. After the window, whole
dispatched batches are recomputed by the plain reference
(``reference.py``) and compared answer by answer; the control is the
reference on half of every hypervector (``bench/calibrate.py``).
"""

from __future__ import annotations

import collections
import time

import numpy as np

QUERY_TAG, WARM_TAG = 10, 11  # generator streams of the seed


def bring_up(cfg: dict, mix: dict, seed: int, pools, compiles, log):
    """The library from ``seed`` (its precursors from a random order's
    ``set_seed``), a query pool of ``count`` spectra for each (pool seed,
    count) of ``pools``, the bank and the server, then the warm-up of every
    program the mix reaches over those pools' precursors. Returns the
    deployment and the pools."""
    import jax

    import adapter
    import gen
    import loadgen

    spec = gen.Spec.from_config(cfg)
    serving = cfg["serving"]
    max_batch = serving["max_batch_size"]
    device = jax.devices()[0]

    def phase(label, t0):
        log(f"set-up {label}: {time.monotonic() - t0:.3f} s")

    t = time.monotonic()
    # a random order's set fixes every precursor: the same work each seed
    random_order = mix.get("precursor_order") == "random"
    lib, table = gen.build_library(
        spec, seed, precursor_seed=mix["set_seed"] if random_order else None)
    phase(f"library ({spec.library_rows} targets + as many decoys, "
          f"{2 * spec.library_rows * spec.dim // 8} packed bytes)", t)
    t = time.monotonic()
    made = [gen.query_pool(spec, s, QUERY_TAG, count, table, max_batch,
                           mix.get("precursor_order", "stratified"),
                           mix.get("set_batches", 0), mix.get("set_seed", 0))
            for s, count in pools]
    warm = gen.query_pool(spec, seed, WARM_TAG,
                          loadgen.warm_pool_size(mix, max_batch), table,
                          max_batch)
    del table
    phase(f"query pool ({sum(map(len, made))} spectra, {len(warm)} for "
          f"warm-up)", t)
    t = time.monotonic()
    dep = adapter.Deployment(lib, serving, cfg["windows"][mix["window"]])
    phase("bank and server", t)
    log(f"device 0 peak memory after the bank build: "
        f"{(device.memory_stats() or {}).get('peak_bytes_in_use', 0)} bytes")
    t = time.monotonic()
    batches = loadgen.warm_batches(
        mix, max_batch, dep.buckets, warm,
        min(float(p.precursor.min()) for p in made),
        max(float(p.precursor.max()) for p in made))
    sweeps = loadgen.warm_up(dep, batches, lambda: compiles[0])
    phase(f"warm-up ({sweeps} sweeps of {len(batches[0])} batches, "
          f"{compiles[0]} programs so far)", t)
    return dep, made


def counters(dep) -> dict:
    """The OMS planner's counters, read as the window opens and closes."""
    return dep.oms_counters()


def describe(rec, dep, pool, w, log) -> None:
    """What this kind's readers take from the record beside the generic
    fields: each request's precursor, the search window and the library's
    sorted precursors. Logs the window's batches by OMS tile budget, and
    each batch's requests and budget in dispatch order."""
    import program_spans

    rec.precursor_of = {r: float(pool.precursor[i])
                        for r, i in w.pool_row.items()}
    rec.window = rec.config["windows"][rec.mix["window"]]
    rec.sorted_prec = np.sort(dep.lib.precursor)
    tiles = program_spans.batch_tiles(rec)
    if tiles is None:
        return
    log(f"window batches by tile budget: "
        f"{dict(sorted(collections.Counter(t for _, t in tiles).items()))}")
    log("window batches (requests x tiles): "
        + " ".join(f"{n}x{t}" for n, t in tiles))


def sweep_fields(rec) -> dict:
    """A knee sweep's window (``bench/sweep.py``): its batches by OMS tile
    budget and the planner's over-scan."""
    import program_spans
    import readers

    tiles = collections.Counter(
        t for _, t in program_spans.batch_tiles(rec) or [])
    return {"tiles": {str(k): v for k, v in sorted(tiles.items())},
            "oms_overscan": readers.oms_overscan(rec)}


def checks(rec, dep, pool, w, batches, control: bool, log
           ) -> tuple[dict, int]:
    """The served answers of ``batches`` (request-id lists in dispatch
    order) against the reference's, and the bank's encoded rows against
    the reference's encoding. With ``control`` the program's comparison
    is logged and the control's answers take the served ones' place.
    Returns {name: {"value", "limit"}} and the requests compared."""
    import reference

    t = time.monotonic()
    lib, cfg = dep.lib, rec.config
    got = {r: vars(s) for r, s in w.served.items()}

    def query(rid):
        i = w.pool_row[rid]
        return pool.levels[i].astype(np.int64), pool.precursor[i]

    def answers(half: bool) -> dict:
        ref = reference.Reference(lib, tol=rec.window["tol"],
                                  open_tol=rec.window["open_tol"],
                                  k=cfg["serving"]["k"], dim=cfg["dim"],
                                  half=half)
        return reference.answer_batches(ref, batches, query,
                                        cfg["serving"]["fdr"])

    want = answers(half=False)
    found = reference.compare(want, got)
    log(f"reference: {found['checked_requests']} requests in "
        f"{len(batches)} batches and {2 * len(lib.checked_rows)} bank rows "
        f"in {time.monotonic() - t:.3f} s")
    if control:
        log(f"program: topk_mismatch {found['topk_mismatch']} fdr_mismatch "
            f"{found['fdr_mismatch']} over {found['checked_requests']} "
            f"requests")
        # the control's answers go where the served ones were
        got.update(answers(half=True))
        found = reference.compare(want, got)
        log(f"control (half the dimensions): topk_mismatch "
            f"{found['topk_mismatch']} fdr_mismatch {found['fdr_mismatch']} "
            f"over {found['checked_requests']} requests")
    return {
        "topk_mismatch": {"value": found["topk_mismatch"], "limit": 0},
        "fdr_mismatch": {"value": found["fdr_mismatch"], "limit": 0},
        "bank_rows_mismatch": {"value": reference.check_bank(lib),
                               "limit": 0},
    }, found["checked_requests"]
