"""Streaming spectral-clustering serving launcher (the paper's second task).

HD-encodes one synthetic spectrum stream per tenant and pushes it through
the clustering endpoint of :class:`~repro.serve.DBSearchServer`
(``submit_cluster``): per-tenant assign-or-spawn against packed centroid
HVs on the device, periodic complete-linkage re-consolidation, sharing
the micro-batch queue / bucket ladder / (optionally) the continuous
scheduler with DB search. Reports spectra/sec, latency, cluster counts,
and — ground truth being synthetic — the paper's clustering quality
metrics (clustered-spectra ratio, incorrect-clustering ratio).

Usage:
  PYTHONPATH=src python -m repro.launch.serve_cluster --reduced
  PYTHONPATH=src python -m repro.launch.serve_cluster --reduced \\
      --tenants 2 --consolidate-every 64 --continuous
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core import SpecPCMConfig, encode_and_pack
from repro.core.hd.clustering import (
    clustered_spectra_ratio,
    incorrect_clustering_ratio,
)
from repro.dist.sharding import set_mesh
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_debug_mesh
from repro.serve import BankRegistry, ClusteringConfig, DBSearchServer
from repro.spectra import SyntheticMSConfig, generate_dataset


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--reduced", action="store_true",
                    help="small sizes for CPU smoke runs")
    ap.add_argument("--hd-dim", type=int, default=None)
    ap.add_argument("--identities", type=int, default=None)
    ap.add_argument("--spectra-per-identity", type=int, default=None)
    ap.add_argument("--max-batch", type=int, default=None)
    ap.add_argument("--flush-ms", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tenants", type=int, default=1,
                    help="independent cluster streams (per-tenant state)")
    ap.add_argument("--threshold-frac", type=float, default=0.36,
                    help="assign threshold as a fraction of D (Hamming "
                         "distance to the nearest centroid; random HVs sit "
                         "near 0.5D, same-identity synthetic spectra near "
                         "0.3D)")
    ap.add_argument("--consolidate-every", type=int, default=0,
                    help="re-run complete linkage over the centroid bank "
                         "every this many assigned spectra (0 disables)")
    ap.add_argument("--no-pack", action="store_true",
                    help="disable the bit-packed popcount distance kernel")
    ap.add_argument("--continuous", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="continuous-batching mode (shared scheduler slots)")
    ap.add_argument("--num-slots", type=int, default=2)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.tenants < 1:
        raise SystemExit("--tenants must be >= 1")
    if args.reduced:
        dim = args.hd_dim or 512
        n_id = args.identities or 24
        per_id = args.spectra_per_identity or 6
        max_batch = args.max_batch or 16
        num_bins = 256
    else:
        dim = args.hd_dim or 2048
        n_id = args.identities or 128
        per_id = args.spectra_per_identity or 8
        max_batch = args.max_batch or 32
        num_bins = 1024

    mesh = make_debug_mesh()
    set_mesh(mesh)
    print(f"mesh: {dict(mesh.shape)}")

    cfg = SpecPCMConfig(hd_dim=dim, mlc_bits=1, num_levels=16, ideal=True,
                        seed=args.seed)
    ccfg = ClusteringConfig(
        dim=dim, threshold=args.threshold_frac * dim,
        consolidate_every=args.consolidate_every,
        pack=False if args.no_pack else "auto")

    streams = {}  # tenant -> (hvs (N, D) int8, identity (N,))
    for t in range(args.tenants):
        tenant = f"tenant{t}"
        ms = SyntheticMSConfig(num_identities=n_id,
                               spectra_per_identity=per_id,
                               num_bins=num_bins, seed=args.seed + 31 * t)
        ds = generate_dataset(ms)
        hvs = np.asarray(encode_and_pack(ds.spectra, cfg), np.int8)
        streams[tenant] = (hvs, np.asarray(ds.identity))
    n_per = n_id * per_id
    print(f"{args.tenants} stream(s) of {n_per} spectra, D={dim}, "
          f"threshold={ccfg.threshold:g} "
          f"({args.threshold_frac:g}*D), packed={ccfg.packed}, "
          f"consolidate_every={args.consolidate_every}, "
          f"mode={'continuous' if args.continuous else 'flush-sync'}")

    server = DBSearchServer(
        BankRegistry(), k=1, max_batch_size=max_batch,
        flush_timeout_s=args.flush_ms / 1e3, buckets=4,
        clustering=ccfg, continuous=args.continuous,
        num_slots=args.num_slots)

    # interleaved round-robin streaming in bursts, arrival order shuffled
    # within each tenant's stream
    rng = np.random.default_rng(args.seed)
    orders = {t: rng.permutation(n_per) for t in streams}
    cursors = {t: 0 for t in streams}
    meta = {}  # rid -> (tenant, stream position)
    done = []
    total = n_per * args.tenants
    sent = 0
    while sent < total:
        burst = int(rng.integers(1, max_batch + 1))
        for _ in range(min(burst, total - sent)):
            tenant = f"tenant{int(rng.integers(args.tenants))}"
            if cursors[tenant] >= n_per:
                tenant = next(t for t in streams if cursors[t] < n_per)
            pos = orders[tenant][cursors[tenant]]
            cursors[tenant] += 1
            rid = server.submit_cluster(streams[tenant][0][pos],
                                        tenant=tenant)
            meta[rid] = (tenant, int(pos))
            sent += 1
        done.extend(server.step())
        while args.continuous and len(server.queue) >= max_batch:
            done.extend(server.step(force=True))
        if rng.random() < 0.3:
            time.sleep(args.flush_ms / 1e3)
            done.extend(server.step())
    done.extend(server.run_until_drained())
    assert len(done) == total, (len(done), total)

    s = server.summary()
    print(f"clustered {s['count']} spectra in {s['batches']} micro-batches "
          f"(mean batch {s['mean_batch']:.1f})")
    print(f"throughput: {s['qps']:.1f} spectra/sec")
    print(f"latency: p50 {s['p50_ms']:.2f} ms, p95 {s['p95_ms']:.2f} ms")

    quality = {}
    for tenant, (hvs, identity) in streams.items():
        cl = server.clusterers[tenant]
        reqs = sorted((r for r in done if meta[r.rid][0] == tenant),
                      key=lambda r: r.rid)
        # labels in *stream* order, remapped to the request's point index
        labels = np.zeros(n_per, np.int64)
        for r in reqs:
            labels[meta[r.rid][1]] = cl.resolve(r.result.cluster_id)
        # cluster ids are spawn-order ints < n_per, so the paper's quality
        # metrics apply directly
        csr = float(clustered_spectra_ratio(labels))
        icr = float(incorrect_clustering_ratio(labels, identity))
        cs = cl.summary()
        quality[tenant] = {"clusters": cs["clusters"],
                           "clustered_ratio": csr,
                           "incorrect_ratio": icr, **cs}
        print(f"  {tenant}: {cs['clusters']} clusters over {n_per} spectra "
              f"({n_id} true identities), {cs['spawned']} spawned, "
              f"{cs['merges']} merges / {cs['consolidations']} "
              f"consolidations; clustered ratio {csr:.3f}, incorrect "
              f"ratio {icr:.3f}")
    s["cluster_quality"] = quality
    return s


if __name__ == "__main__":
    main()
