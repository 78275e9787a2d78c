"""On-chip smoke test: spectral-library search served at iPRG2012 scale.

Builds the iPRG2012 library (1,162,392 targets plus as many decoys, D=8192
SLC hypervectors, bit-packed) from ``--seed`` in bounded chunks on the
device, then drives the main serving path once through
``DBSearchServer`` with the fused Pallas kernels, checking every result
bit for bit against an independent oracle:

  * the encoders: 384 bank rows of the first pass (decoys too) and all
    512 queries — against the gather oracle ``encode_batch_reference``;
  * exact search: 512 queries, fused streaming top-k, continuous batching
    — against the unfused jnp top-k over the same bank, plus a numpy
    XOR+popcount recomputation for 8 queries;
  * fused end to end: the same queries as raw level vectors through the
    fused encode->search kernel — against the staged encode-then-search
    path;
  * open-modification search: 512 queries with precursors on the
    precursor-sorted bank through the banded kernel — against
    sentinel-masking the full score matrix.

With ``--chips 4`` it runs only the sharded phase: the bank row-sharded
over ``'model'`` of a (1, 4) mesh, exact and OMS search, each compared
with a one-device search of the same queries on device 0.

It refuses to run without a TPU. The last line of its output is one JSON
object naming the device; any failed check exits non-zero before it.

Usage:
  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # the sharded phase on four chips
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.hd.encoding import (  # noqa: E402
    encode_batch_reference,
    encode_bitpacked,
    encode_levels_batch,
    quantize_levels,
)
from repro.core.hd.similarity import bitpack_bipolar  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.serve import (  # noqa: E402
    DBSearchServer,
    OMSConfig,
    OMSPlan,
    QueryEncoder,
    encode_queries,
    oms_plan,
    oms_search_encoded,
    search_database_encoded,
    search_database_levels,
    shard_database,
)
from repro.spectra import SyntheticMSConfig, generate_dataset  # noqa: E402

# iPRG2012 (core/imc/energy.py): 1,162,392 library spectra, searched at
# D=8192 (HDEncoderConfig), candidate fraction 0.025 for OMS
IDENTITIES = 145_299  # peptide templates
PASSES = 8            # seeded replicate passes: 8 x 145,299 = 1,162,392
DIM = 8192
NUM_BINS = 1024
NUM_LEVELS = 16
K = 4
NUM_QUERIES = 512
BATCH = 128
NUMPY_CHECKED = 8
# bank rows of pass 1 checked against the gather oracle: the first rows,
# both sides of the first 8192-row encoder chunk boundary, and the ragged
# last chunk (encode_bitpacked)
ENCODER_CHECKED = np.r_[0:128, 8192 - 64:8192 + 64,
                        IDENTITIES - 128:IDENTITIES]
ORACLE_ROWS = 16  # encode_batch_reference holds (rows, F, D) int32
# OMS window: query - ref in (-2.5, +28); 30.5 of the 1,200 Da precursor
# range, i.e. a candidate fraction near iPRG2012's 0.025
OMS = OMSConfig(tol=2.5, open_tol=28.0)
MOD_MASS = (4.0, 20.0)  # modified queries stay inside the open window


def fail(what: str):
    raise SystemExit(f"chip_smoke: FAILED: {what}")


def check(ok: bool, what: str) -> None:
    if not ok:
        fail(what)


def setup(label: str, t0: float) -> None:
    print(f"set-up {label}: {time.perf_counter() - t0:.2f} s", flush=True)


def assert_kernel(label: str, fn, *args) -> None:
    """The search as lowered for this device must call a Mosaic kernel —
    an interpret-mode fallback lowers to plain XLA loops instead. Pass the
    bank as an argument: a closed-over bank would be lowered as a
    constant."""
    text = jax.jit(fn).lower(*args).as_text()
    check("tpu_custom_call" in text, f"{label}: no tpu_custom_call in the "
          "lowered search (kernel not compiled for the chip)")
    print(f"{label}: tpu_custom_call confirmed", flush=True)


@dataclasses.dataclass
class Library:
    targets: jax.Array       # (N, D/32) uint32
    decoys: jax.Array        # (N, D/32) uint32, m/z-reversed spectra
    precursor: np.ndarray    # (N,) float32
    encoder: QueryEncoder
    q_levels: np.ndarray     # (Q, F) int32 raw query spectra
    q_hv: np.ndarray         # (Q, D) int8 encoded queries
    q_prec: np.ndarray       # (Q,) float32
    q_ident: np.ndarray      # (Q,) template id


@functools.partial(jax.jit, static_argnums=0)
def generate_levels(cfg: SyntheticMSConfig, seed, rows):
    """One synthetic pass as one compiled program (the seed is traced, so
    every pass reuses it): quantized levels, precursors, template ids, and
    the raw spectra of ``rows`` for the encoder check."""
    ds = generate_dataset(dataclasses.replace(cfg, seed=seed))
    return (quantize_levels(ds.spectra, NUM_LEVELS), ds.precursor,
            ds.identity, ds.spectra[rows])


reference_encode = jax.jit(encode_batch_reference)


def check_encoder(label: str, feats, got, enc: QueryEncoder) -> None:
    """Packed encoder output for raw spectra ``feats`` must equal the
    gather oracle ``encode_batch_reference``, taken ORACLE_ROWS at a time
    for its (rows, F, D) intermediate."""
    want = jnp.concatenate([bitpack_bipolar(reference_encode(
        feats[lo:lo + ORACLE_ROWS], enc.id_hvs, enc.level_hvs))
        for lo in range(0, feats.shape[0], ORACLE_ROWS)])
    bad = int(jnp.sum(jnp.any(got != want, axis=1)))
    print(f"{label} vs encode_batch_reference: {bad} mismatches over "
          f"{feats.shape[0]} rows", flush=True)
    check(bad == 0, f"{label}: {bad} rows differ from encode_batch_reference")


def build_library(seed: int) -> Library:
    """Generate and encode the library pass by pass: only packed words of
    the whole bank ever exist on the device."""
    enc = QueryEncoder.from_config(dim=DIM, num_features=NUM_BINS,
                                   num_levels=NUM_LEVELS, seed=seed)
    base = SyntheticMSConfig(num_identities=IDENTITIES,
                             spectra_per_identity=1, num_bins=NUM_BINS)
    targets, decoys, prec = [], [], []
    checked = jnp.asarray(ENCODER_CHECKED)
    for p in range(PASSES):
        t0 = time.perf_counter()
        levels, precursor, _, feats = generate_levels(
            base, seed * 1000 + p, checked)
        targets.append(encode_bitpacked(levels, enc.id_hvs, enc.level_hvs))
        # decoys reverse the m/z axis (make_decoys); quantizing commutes
        decoys.append(encode_bitpacked(levels[:, ::-1], enc.id_hvs,
                                       enc.level_hvs))
        prec.append(np.asarray(precursor, np.float32))
        del levels
        jax.block_until_ready(decoys[-1])
        setup(f"library pass {p + 1}/{PASSES} (generate + encode "
              f"{2 * IDENTITIES} rows)", t0)
        if p == 0:
            check_encoder("bank encoder, target rows", feats,
                          targets[-1][checked], enc)
            check_encoder("bank encoder, decoy rows", feats[:, ::-1],
                          decoys[-1][checked], enc)
    t0 = time.perf_counter()
    targets = jnp.concatenate(targets)
    decoys = jnp.concatenate(decoys)
    jax.block_until_ready(decoys)
    setup("library concatenate", t0)

    t0 = time.perf_counter()
    qcfg = dataclasses.replace(base, modification_rate=0.3,
                               modification_mass_range=MOD_MASS)
    pick = np.sort(np.random.default_rng(seed).choice(
        IDENTITIES, NUM_QUERIES, replace=False))
    levels, precursor, identity, q_feats = generate_levels(
        qcfg, seed * 1000 + 999, jnp.asarray(pick))
    q_levels = levels[jnp.asarray(pick)]
    q_hv = encode_levels_batch(q_levels, enc.id_hvs, enc.level_hvs)
    check_encoder("query encoder", q_feats, bitpack_bipolar(q_hv), enc)
    lib = Library(targets=targets, decoys=decoys,
                  precursor=np.concatenate(prec), encoder=enc,
                  q_levels=np.asarray(q_levels, np.int32),
                  q_hv=np.asarray(q_hv, np.int8),
                  q_prec=np.asarray(precursor, np.float32)[pick],
                  q_ident=np.asarray(identity)[pick])
    del levels
    setup(f"{NUM_QUERIES} queries (generate + encode)", t0)
    rows = 2 * int(targets.shape[0])
    print(f"bank: {rows} rows ({int(targets.shape[0])} targets + "
          f"{int(decoys.shape[0])} decoys), D={DIM}, "
          f"{rows * DIM // 8} packed bytes", flush=True)
    return lib


def serve(server: DBSearchServer, queries, precursors=None):
    """Submit every query, drain the server, return results in submit
    order as ((Q, k) indices, (Q, k) scores, [QueryResult])."""
    rids = [server.submit(q, precursor=None if precursors is None
                          else float(precursors[i]))
            for i, q in enumerate(queries)]
    done = {r.rid: r.result for r in server.run_until_drained()}
    check(len(done) == len(rids), f"served {len(done)} of {len(rids)}")
    res = [done[rid] for rid in rids]
    return (np.stack([r.indices for r in res]),
            np.stack([r.scores for r in res]), res)


def chunked(fn, n: int):
    """Run ``fn(lo, hi)`` over query chunks and stack (idx, vals)."""
    out = [fn(lo, min(lo + BATCH, n)) for lo in range(0, n, BATCH)]
    return (np.concatenate([np.asarray(i) for i, _ in out]),
            np.concatenate([np.asarray(v) for _, v in out]))


def compare(label: str, got, want) -> None:
    bad = int(np.sum(np.any((got[0] != want[0]) | (got[1] != want[1]),
                            axis=1)))
    print(f"{label}: {bad} mismatches over {got[0].shape[0]} queries",
          flush=True)
    check(bad == 0, f"{label}: {bad} mismatched queries")


def report_ids(label: str, res, lib: Library) -> None:
    acc = [(i, r.match) for i, r in enumerate(res) if r.match >= 0]
    good = sum(int(m % IDENTITIES == lib.q_ident[i]) for i, m in acc)
    print(f"{label}: identified at 1% FDR {len(acc)}/{len(res)} queries, "
          f"{good} with the right template", flush=True)
    check(good > 0, f"{label}: no correct identification")


def numpy_topk(db, q_enc: np.ndarray, k: int):
    """XOR+popcount top-k on the host, ties to the lower row."""
    bank = np.asarray(db.data[:db.num_rows]).view(np.uint64)
    qs = np.ascontiguousarray(q_enc).view(np.uint64)
    idx, vals = [], []
    for q in qs:
        dist = np.concatenate([
            np.bitwise_count(bank[s:s + (1 << 18)] ^ q).sum(
                axis=1, dtype=np.int32)
            for s in range(0, bank.shape[0], 1 << 18)])
        score = db.dim - 2 * dist
        top = np.lexsort((np.arange(score.shape[0]), -score))[:k]
        idx.append(top)
        vals.append(score[top])
    return np.stack(idx).astype(np.int32), np.stack(vals).astype(np.int32)


def exact_phases(lib: Library) -> None:
    t0 = time.perf_counter()
    db = shard_database(lib.targets, decoys=lib.decoys, fused=True,
                        block_q=BATCH)
    jax.block_until_ready(db.data)
    setup("exact bank", t0)
    q_enc = encode_queries(db, jnp.asarray(lib.q_hv))
    assert_kernel("exact search", lambda data, q: search_database_encoded(
        dataclasses.replace(db, data=data), q, K), db.data, q_enc[:BATCH])

    t0 = time.perf_counter()
    server = DBSearchServer(db, k=K, max_batch_size=BATCH, continuous=True)
    idx, vals, res = serve(server, lib.q_hv)
    setup("exact serving (compile included)", t0)
    # jitted, so the (Q, R, W) XOR fuses into the popcount reduction
    oracle = jax.jit(lambda data, q: search_database_encoded(
        dataclasses.replace(db, data=data, fused=False), q, K))
    compare("exact search vs unfused jnp top-k", (idx, vals), chunked(
        lambda lo, hi: oracle(db.data, q_enc[lo:hi]), NUM_QUERIES))
    compare(f"exact search vs numpy XOR+popcount ({NUMPY_CHECKED} queries)",
            (idx[:NUMPY_CHECKED], vals[:NUMPY_CHECKED]),
            numpy_topk(db, np.asarray(q_enc[:NUMPY_CHECKED]), K))
    report_ids("exact search", res, lib)

    enc = lib.encoder
    levels = jnp.asarray(lib.q_levels)
    assert_kernel("fused encode->search", lambda data, lv: (
        search_database_levels(dataclasses.replace(db, data=data), enc, lv,
                               K, fused_e2e=True)), db.data, levels[:BATCH])
    t0 = time.perf_counter()
    server = DBSearchServer(db, k=K, max_batch_size=BATCH, continuous=True,
                            encoder=enc, fused_e2e=True)
    idx, vals, res = serve(server, lib.q_levels)
    setup("fused end-to-end serving (compile included)", t0)
    compare("fused end to end vs staged encode-then-search", (idx, vals),
            chunked(lambda lo, hi: search_database_levels(
                db, enc, levels[lo:hi], K, fused_e2e=False), NUM_QUERIES))
    report_ids("fused end to end", res, lib)


def oms_phase(lib: Library) -> None:
    t0 = time.perf_counter()
    db = shard_database(lib.targets, decoys=lib.decoys, fused=True,
                        precursor=lib.precursor, block_q=BATCH)
    jax.block_until_ready(db.data)
    setup("precursor-sorted OMS bank", t0)
    q_enc = encode_queries(db, jnp.asarray(lib.q_hv))
    order = np.argsort(lib.q_prec[:BATCH], kind="stable")
    plan = oms_plan(db, lib.q_prec[:BATCH][order], OMS)
    assert_kernel("OMS banded search", lambda data, q: oms_search_encoded(
        dataclasses.replace(db, data=data), q, plan, K), db.data,
        q_enc[:BATCH][jnp.asarray(order)])

    t0 = time.perf_counter()
    server = DBSearchServer(db, k=K, max_batch_size=BATCH, continuous=True,
                            oms=OMS)
    idx, vals, res = serve(server, lib.q_hv, lib.q_prec)
    setup("OMS serving (compile included)", t0)
    s = server.summary()["oms"]
    print(f"OMS window (-{OMS.tol:g}, +{OMS.open_tol:g}): candidate "
          f"fraction {s['candidate_fraction']:.4f}, scanned fraction "
          f"{s['scanned_fraction']:.4f}, {s['no_candidate']} queries with "
          f"empty windows", flush=True)
    oracle = jax.jit(masked_oms_oracle(db))
    compare("OMS vs sentinel-masked full score matrix", (idx, vals), chunked(
        lambda lo, hi: oracle(db.data, q_enc[lo:hi],
                              *oms_bands(db, lib.q_prec[lo:hi])),
        NUM_QUERIES))
    report_ids("OMS search", res, lib)


def oms_bands(db, prec: np.ndarray):
    plan = oms_plan(db, prec, OMS)
    return jnp.asarray(plan.starts), jnp.asarray(plan.lens)


def masked_oms_oracle(db):
    """The unfused OMS route over ``db``'s layout: every query's full score
    row, sentinel-masked outside its precursor bands, then ``lax.top_k``.
    Bank and bands are arguments, so one compile serves every chunk."""
    def run(data, q, starts, lens):
        plan = OMSPlan(starts=starts, lens=lens, num_tiles=1,
                       scanned_fraction=1.0, candidate_fraction=0.0)
        return oms_search_encoded(
            dataclasses.replace(db, data=data, fused=False), q, plan, K)
    return run


def free_served_banks() -> None:
    """A served bank sits in a reference cycle (DBSearchServer and its
    SearchExecutor point at each other), so it outlives its phase until
    the cyclic collector runs. Collect it before the next phase builds
    another bank, so peak device memory does not depend on when that is."""
    gc.collect()


def sharded_phase(lib: Library, n_chips: int) -> None:
    mesh = make_mesh((1, n_chips), ("data", "model"))
    dev0 = jax.devices()[0]
    for oms in (False, True):
        label = "sharded OMS" if oms else "sharded exact"
        kw = dict(decoys=lib.decoys, fused=True, block_q=BATCH,
                  precursor=lib.precursor if oms else None)
        t0 = time.perf_counter()
        db = shard_database(lib.targets, mesh=mesh, **kw)
        one = shard_database(lib.targets, **kw)
        jax.block_until_ready((db.data, one.data))
        setup(f"{label} banks", t0)
        shards = sorted((s.device.id, s.index[0].start, s.index[0].stop)
                        for s in db.data.addressable_shards)
        print(f"{label}: bank shards (device, rows) "
              f"{[(d, f'{a}:{b}') for d, a, b in shards]}", flush=True)
        check(len({d for d, _, _ in shards}) == n_chips,
              f"{label}: bank is not spread over {n_chips} devices")
        check(one.data.devices() == {dev0},
              f"{label}: one-device bank not on device 0")
        q_enc = encode_queries(one, jnp.asarray(lib.q_hv))
        if oms:
            order = np.argsort(lib.q_prec[:BATCH], kind="stable")
            plan = oms_plan(db, lib.q_prec[:BATCH][order], OMS)
            assert_kernel(label, lambda data, q: oms_search_encoded(
                dataclasses.replace(db, data=data), q, plan, K), db.data,
                q_enc[:BATCH][jnp.asarray(order)])
        else:
            assert_kernel(label, lambda data, q: search_database_encoded(
                dataclasses.replace(db, data=data), q, K), db.data,
                q_enc[:BATCH])

        t0 = time.perf_counter()
        server = DBSearchServer(db, k=K, max_batch_size=BATCH,
                                continuous=True, oms=OMS if oms else None)
        idx, vals, res = serve(server, lib.q_hv, lib.q_prec if oms else None)
        setup(f"{label} serving (compile included)", t0)
        if oms:
            want = chunked(lambda lo, hi: oms_search_encoded(
                one, q_enc[lo:hi], oms_plan(one, lib.q_prec[lo:hi], OMS), K),
                NUM_QUERIES)
        else:
            want = chunked(lambda lo, hi: search_database_encoded(
                one, q_enc[lo:hi], K), NUM_QUERIES)
        compare(f"{label} vs one-device search on device 0", (idx, vals),
                want)
        report_ids(label, res, lib)
        del db, one, server
        free_served_banks()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase on a (1, 4) mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found {platform!r} "
              f"devices; it does not run on the CPU", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    # block sizes come from the kernel defaults, never a tuning table
    os.environ.pop("REPRO_TUNING_TABLE", None)
    cache_dir = enable_compile_cache()
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    kind = devices[0].device_kind
    print(f"device: platform={platform} kind={kind} count={len(devices)}",
          flush=True)

    t_all = time.perf_counter()
    lib = build_library(args.seed)
    if args.chips == 1:
        exact_phases(lib)
        free_served_banks()
        oms_phase(lib)
    else:
        sharded_phase(lib, args.chips)
    stats = devices[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"device 0 peak memory: {stats['peak_bytes_in_use']} bytes",
              flush=True)
    print(f"compile cache {cache_dir}: {cache_events['hits']} programs "
          f"read from it, {cache_events['misses']} not found there",
          flush=True)
    setup("whole run", t_all)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
