"""Sharded, multi-tenant DB-search serving launcher.

Builds the debug mesh, HD-encodes one synthetic spectral library
(+ decoys) per tenant, registers them in a lazy
:class:`~repro.serve.cache.BankRegistry` (banks shard onto the 'model'
axis on first use; tenant 0 is pinned hot), then streams bursty,
hot-tenant-skewed queries — drawn with replacement, so repeats hit the
content-hash :class:`~repro.serve.cache.QueryHVCache` — through the
micro-batching :class:`~repro.serve.DBSearchServer`, batching over
'data' with shape-bucketed padding and a per-flush fairness cap. Reports
queries/sec, aggregate and per-tenant p50/p95 latency, cache hit rate,
bank builds/evictions, and identification quality at the requested FDR.

Usage:
  PYTHONPATH=src python -m repro.launch.serve_db --reduced
  PYTHONPATH=src python -m repro.launch.serve_db --reduced --tenants 4 \\
      --cache-mb 16 --buckets 3 --fairness-cap 8
"""

from __future__ import annotations

import argparse
import time

import jax.numpy as jnp
import numpy as np

from repro.core import SpecPCMConfig, encode_and_pack
from repro.core.hd.encoding import quantize_levels
from repro.dist.sharding import set_mesh
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_debug_mesh
from repro.serve import (
    BankRegistry,
    DBSearchServer,
    OMSConfig,
    QueryEncoder,
    oms_plan,
    oms_search_levels,
    oms_search_with_fdr,
    search_database_levels,
    search_with_fdr,
)
from repro.serve.db_search import fdr_route
from repro.spectra import SyntheticMSConfig, generate_dataset
from repro.spectra.fdr import make_decoys
from repro.spectra.synthetic import generate_query_set


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--reduced", action="store_true",
                    help="small sizes for CPU smoke runs")
    ap.add_argument("--hd-dim", type=int, default=None)
    ap.add_argument("--identities", type=int, default=None)
    ap.add_argument("--refs-per-identity", type=int, default=None)
    ap.add_argument("--queries", type=int, default=None,
                    help="requests per tenant")
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--max-batch", type=int, default=None)
    ap.add_argument("--flush-ms", type=float, default=5.0)
    ap.add_argument("--fdr", type=float, default=0.01)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-pack", action="store_true",
                    help="disable the bit-packed XOR+popcount shard path")
    ap.add_argument("--fused", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="route per-shard search through the fused "
                         "streaming top-k Pallas kernel (O(Q*k) candidate "
                         "traffic; interpret-mode — slow — off TPU)")
    ap.add_argument("--tenants", type=int, default=1,
                    help="number of tenant banks (tenant 0 is pinned hot)")
    ap.add_argument("--cache-mb", type=float, default=64.0,
                    help="query-HV cache byte budget in MiB (0 disables)")
    ap.add_argument("--buckets", type=int, default=4,
                    help="batch-shape buckets (geometric ladder up to "
                         "--max-batch; 1 = always pad to max)")
    ap.add_argument("--fairness-cap", type=int, default=None,
                    help="max requests one tenant may take per flush while "
                         "others wait (default: no cap)")
    ap.add_argument("--max-banks", type=int, default=None,
                    help="LRU-evict cold built banks beyond this many "
                         "(default: keep all)")
    ap.add_argument("--oms", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="open-modification serving mode: banks are "
                         "precursor-sorted and each query scans only its "
                         "precursor window (query - ref in "
                         "(-tolerance, open-tol))")
    ap.add_argument("--tolerance", type=float, default=20.0,
                    help="precursor tolerance on the light side (and both "
                         "sides for exact search)")
    ap.add_argument("--open-tol", type=float, default=200.0,
                    help="how much heavier than a reference an OMS query "
                         "may be (the modification-mass budget)")
    ap.add_argument("--continuous", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="continuous-batching mode: keep --num-slots "
                         "batches in flight and admit queued requests the "
                         "moment a slot frees, instead of flush-and-wait "
                         "(collapses tail latency; --flush-ms is inert)")
    ap.add_argument("--num-slots", type=int, default=2,
                    help="in-flight batch slots for --continuous (2 = "
                         "double-buffered host prep vs device search)")
    ap.add_argument("--fused-e2e", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="submit raw quantized spectra and run the fused "
                         "encode->pack->search kernel per shard (one device "
                         "dispatch; the query HV never touches HBM)")
    ap.add_argument("--append", type=float, default=0.0, metavar="FRAC",
                    help="hold this fraction of every bank out of the "
                         "initial registration and stream it back in with "
                         "server.append() halfway through the run — "
                         "searches after the append take the exact merged "
                         "base+delta path (0 disables)")
    ap.add_argument("--compact-threshold", type=float, default=None,
                    help="fold a tenant's delta into its packed base when "
                         "the delta exceeds this fraction of total rows "
                         "(default: never compact)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.tenants < 1:
        raise SystemExit("--tenants must be >= 1")
    if args.reduced:
        dim = args.hd_dim or 512
        n_id = args.identities or 48
        per_id = args.refs_per_identity or 2
        n_q = args.queries or 64
        max_batch = args.max_batch or 16
        num_bins = 256
    else:
        dim = args.hd_dim or 2048
        n_id = args.identities or 256
        per_id = args.refs_per_identity or 4
        n_q = args.queries or 256
        max_batch = args.max_batch or 32
        num_bins = 1024

    mesh = make_debug_mesh()
    set_mesh(mesh)
    print(f"mesh: {dict(mesh.shape)}")

    # SLC (1-bit) encoding keeps the HVs bipolar so the server can take the
    # bit-packed shard path whenever D % 32 == 0.
    cfg = SpecPCMConfig(hd_dim=dim, mlc_bits=1, num_levels=16, ideal=True,
                        seed=args.seed)
    pack = False if args.no_pack else "auto"
    registry = BankRegistry(mesh=mesh, pack=pack, max_banks=args.max_banks,
                            fused=args.fused)

    # OMS traffic: modified queries carry a heavier precursor (a phospho-like
    # mass addition), the case the open window exists for.
    oms_cfg = (OMSConfig(tol=args.tolerance, open_tol=args.open_tol)
               if args.oms else None)
    mod_range = (60.0, 0.75 * args.open_tol) if args.oms else (0.0, 0.0)

    if not 0.0 <= args.append < 1.0:
        raise SystemExit("--append must be in [0, 1)")
    datasets, query_pools, precursor_pools = {}, {}, {}
    holdouts = {}  # tenant -> (refs, decoys, precursor) appended mid-run
    for t in range(args.tenants):
        tenant = f"tenant{t}"
        ms = SyntheticMSConfig(num_identities=n_id,
                               spectra_per_identity=per_id,
                               num_bins=num_bins, seed=args.seed + 31 * t,
                               modification_mass_range=mod_range)
        ds = generate_dataset(ms)
        refs_hv = encode_and_pack(ds.spectra, cfg)
        decoys_hv = encode_and_pack(make_decoys(ds.spectra), cfg)
        prec = np.asarray(ds.precursor) if args.oms else None
        n_refs = int(refs_hv.shape[0])
        keep = n_refs - int(args.append * n_refs)
        if args.append and keep < n_refs:
            # hold out a *suffix* so append restores the original row
            # order — the identity arrays keep indexing matches directly
            holdouts[tenant] = (
                np.asarray(refs_hv[keep:], np.int8),
                np.asarray(decoys_hv[keep:], np.int8),
                None if prec is None else prec[keep:].astype(np.float32))
            refs_hv, decoys_hv = refs_hv[:keep], decoys_hv[:keep]
            prec = None if prec is None else prec[:keep]
        registry.register(tenant, refs_hv, decoys=decoys_hv, pin=t == 0,
                          precursor=prec)
        qs = generate_query_set(ds, ms, num_queries=n_q,
                                seed=args.seed + 31 * t + 1)
        datasets[tenant] = (np.asarray(ds.identity), np.asarray(qs.identity))
        if args.fused_e2e:
            # raw quantized spectra: the server encodes on the device, fused
            query_pools[tenant] = np.asarray(
                quantize_levels(qs.spectra, cfg.num_levels), np.int32)
        else:
            query_pools[tenant] = np.asarray(encode_and_pack(qs.spectra, cfg))
        precursor_pools[tenant] = np.asarray(qs.precursor, np.float32)
    print(f"{args.tenants} tenant bank(s) registered (lazy; built on first "
          f"request), D={dim}, pack={pack}, fused={args.fused}, "
          f"oms={args.oms}, fused_e2e={args.fused_e2e}, "
          f"mode={'continuous' if args.continuous else 'flush-sync'}")

    # every tenant encodes with the same SpecPCMConfig, so one query-side
    # codebook bundle serves the whole fleet (bit-identical to the
    # encode_and_pack the banks were built with: mlc_bits=1 packs to
    # identity)
    encoder = (QueryEncoder.from_config(
        dim=dim, num_features=num_bins, num_levels=cfg.num_levels,
        seed=args.seed) if args.fused_e2e else None)

    server = DBSearchServer(
        registry, k=args.k, fdr=args.fdr, max_batch_size=max_batch,
        flush_timeout_s=args.flush_ms / 1e3,
        cache_bytes=int(args.cache_mb * 2**20) or None,
        buckets=args.buckets, fairness_cap=args.fairness_cap, oms=oms_cfg,
        encoder=encoder, fused_e2e=args.fused_e2e,
        continuous=args.continuous, num_slots=args.num_slots,
        compact_threshold=args.compact_threshold)

    # warm the jit cache on the hot tenant (search + FDR routing) for the
    # largest bucket so latency numbers measure serving, not compile; cold
    # tenants pay their lazy shard+compile on first flush by design.
    db0 = registry.get("tenant0")
    warm_prec = None
    if args.oms:
        warm_prec = precursor_pools["tenant0"][:max_batch]
        if warm_prec.shape[0] < max_batch:
            warm_prec = np.resize(warm_prec, max_batch)
        warm_prec = np.sort(warm_prec)
    if args.fused_e2e:
        warm_q = jnp.zeros((max_batch, num_bins), jnp.int32)
        if args.oms:
            plan = oms_plan(db0, warm_prec, oms_cfg)
            idx, vals = oms_search_levels(db0, encoder, warm_q, plan,
                                          args.k, fused_e2e=True)
            fdr_route(db0, idx, vals, fdr=args.fdr,
                      valid=jnp.asarray(plan.has_candidate))
        else:
            idx, vals = search_database_levels(db0, encoder, warm_q, args.k,
                                               fused_e2e=True)
            fdr_route(db0, idx, vals, fdr=args.fdr)
    elif args.oms:
        oms_search_with_fdr(db0, jnp.zeros((max_batch, dim), jnp.int8),
                            warm_prec, k=args.k, fdr=args.fdr, cfg=oms_cfg)
    else:
        search_with_fdr(db0, jnp.zeros((max_batch, dim), jnp.int8), k=args.k,
                        fdr=args.fdr)

    # bursty, hot-tenant-skewed traffic; queries drawn WITH replacement so
    # repeats exercise the content-hash cache.
    rng = np.random.default_rng(args.seed)
    tenant_names = list(query_pools)
    # tenant 0 gets ~half the traffic, the rest split the remainder
    probs = np.asarray([2.0] + [1.0] * (args.tenants - 1)
                       if args.tenants > 1 else [1.0])
    probs = probs / probs.sum()
    total = n_q * args.tenants
    meta = {}  # rid -> (tenant, query row)
    done = []
    sent = 0
    while sent < total:
        if holdouts and sent >= total // 2:
            # stream the held-out rows back in: every later flush takes
            # the exact merged base+delta path (until compaction, if on)
            t0 = time.perf_counter()
            for tenant, (h_refs, h_dec, h_prec) in holdouts.items():
                server.append(tenant, h_refs, h_dec, precursor=h_prec)
            dt = time.perf_counter() - t0
            print(f"appended {sum(h[0].shape[0] + h[1].shape[0] for h in holdouts.values())} "
                  f"rows across {len(holdouts)} tenant(s) in {dt * 1e3:.1f} ms")
            holdouts = {}
        burst = int(rng.integers(1, max_batch + 1))
        for _ in range(min(burst, total - sent)):
            tenant = tenant_names[int(rng.choice(args.tenants, p=probs))]
            qi = int(rng.integers(0, query_pools[tenant].shape[0]))
            rid = server.submit(
                query_pools[tenant][qi], tenant=tenant,
                precursor=(float(precursor_pools[tenant][qi])
                           if args.oms else None))
            meta[rid] = (tenant, qi)
            sent += 1
        done.extend(server.step())
        # continuous mode decouples submission from device completion;
        # with no pacing the driver is an infinite-rate open loop and
        # latency just measures overload depth. Closed-loop backpressure
        # (block-retire once the backlog exceeds a bucket) keeps the run
        # below saturation so the numbers measure scheduling.
        while args.continuous and len(server.queue) >= max_batch:
            done.extend(server.step(force=True))
        if rng.random() < 0.3:  # idle gap: lets the flush timeout fire
            time.sleep(args.flush_ms / 1e3)
            done.extend(server.step())
    done.extend(server.run_until_drained())
    assert len(done) == total, (len(done), total)

    accepted = 0
    correct = 0
    for r in done:
        tenant, qi = meta[r.rid]
        if r.result.match >= 0:
            accepted += 1
            ref_ident, q_ident = datasets[tenant]
            correct += int(ref_ident[r.result.match] == q_ident[qi])

    s = server.summary()
    print(f"served {s['count']} queries in {s['batches']} micro-batches "
          f"(mean batch {s['mean_batch']:.1f}; "
          f"bucket usage {s['buckets']})")
    print(f"throughput: {s['qps']:.1f} queries/sec")
    print(f"latency: p50 {s['p50_ms']:.2f} ms, p95 {s['p95_ms']:.2f} ms, "
          f"mean {s['mean_ms']:.2f} ms (queue wait p50 "
          f"{s['queue_wait_p50_ms']:.2f} ms, p95 "
          f"{s['queue_wait_p95_ms']:.2f} ms)")
    sched = s.get("scheduler")
    if sched is not None:
        print(f"scheduler: {sched['num_slots']} slots, "
              f"{sched['dispatched_batches']} dispatched / "
              f"{sched['retired_batches']} retired batches, "
              f"{sched['cancellations']} cancellations")
    qc = s["query_cache"]
    if qc is not None:
        print(f"query-HV cache: {qc['hits']} hits / {qc['misses']} misses "
              f"(hit rate {qc['hit_rate']:.1%}), {qc['entries']} entries, "
              f"{qc['bytes'] / 2**20:.2f}/{qc['capacity_bytes'] / 2**20:.0f} "
              f"MiB, {qc['evictions']} evictions")
    b = s["banks"]
    print(f"banks: {b['built']}/{b['registered']} built ({b['builds']} "
          f"builds, {b['evictions']} evictions, {b['pinned']} pinned)")
    if args.append:
        ing = s["ingest"]
        print(f"ingest: {b['appends']} appends, {b['compactions']} "
              f"compactions, {b['delta_rows']} delta rows pending "
              f"(compact threshold {ing['compact_threshold']})")
    for tenant in sorted(s["tenants"]):
        ts = s["tenants"][tenant]
        print(f"  {tenant}: {ts['count']} reqs, p50 {ts['p50_ms']:.2f} ms, "
              f"p95 {ts['p95_ms']:.2f} ms, "
              f"cache hit rate {ts['cache_hit_rate']:.1%}")
    o = s.get("oms")
    if o is not None:
        print(f"oms: window (-{o['tol']:g}, +{o['open_tol']:g}), candidate "
              f"fraction {o['candidate_fraction']:.3f}, scanned fraction "
              f"{o['scanned_fraction']:.3f}, {o['no_candidate']} queries "
              f"with empty windows")
    print(f"identified at {args.fdr:.0%} FDR: {accepted}/{total} "
          f"({correct} correct identity)")
    return s


if __name__ == "__main__":
    main()
