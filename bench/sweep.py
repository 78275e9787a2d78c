"""The knee sweep of an open-loop cell: one deployment, warmed once, then a
window at each offered rate, each with a query pool and arrivals of its
own seed. Prints one JSON line per rate: requests due and answered, p50 and
p95 latency, the queue's wait, batches, their fill and sizes, the requests
still queued when the window closed, the programs compiled inside the
window, and what the cell's kind adds (its optional ``sweep_fields``; for
``oms_search``, the OMS over-scan and tile budgets).

    python3 bench/sweep.py --workload iprg2012.oms.poisson.best_order \
        --seed 1 --seconds 10 --rates 800,840,882

The knee is the highest rate at which every request is answered and p95
stays under the limit; a cell offers a fixed share of it. The
benchmark's own runs never do this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import readers  # noqa: E402
import run  # noqa: E402


def main(argv=None, *, root: Path | None = None,
         require_tpu: bool = True) -> int:
    """``root`` and ``require_tpu`` are for the tests, as in run.py."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    root = Path(root or HERE.parent)
    _, cfg, mix, _, _, kind = run.load_cell(root, args.workload)
    if mix["arrival"] != "poisson":
        raise SystemExit(f"sweep.py: {args.workload} is not an open loop")

    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        print("sweep.py: needs a TPU", file=sys.stderr)
        return 2
    compiles = run.compile_cache(root)

    import loadgen

    pools = [(args.seed + i, loadgen.pool_size(dict(mix, rate_per_s=r),
                                               args.seconds))
             for i, r in enumerate(rates)]
    dep, pools = kind.bring_up(cfg, mix, args.seed, pools, compiles, run.log)
    for i, (rate, pool) in enumerate(zip(rates, pools)):
        w, rec = run.measure(kind, dep, dict(mix, rate_per_s=rate), pool,
                             args.seconds, args.seed + i, compiles, cfg)
        queued = dep.pending()
        loadgen.drain(dep, w)
        rec.setup_s = 0.0
        sizes = sorted(len(b.rids) for b in rec.window_batches)
        row = {"rate_per_s": rate, "seed": args.seed + i,
               "due": len(w.attempted),
               "answered": sum(r in w.served for r in w.attempted),
               "p50_ms": run.end_to_end("p50_ms", rec, kind),
               "p95_ms": run.end_to_end("p95_ms", rec, kind),
               "queue_wait_p95_ms": readers.queue_wait_p95_ms(rec),
               "batches": len(sizes), "batch_fill": readers.batch_fill(rec),
               "smallest_batch": min(sizes, default=0),
               "median_batch": sizes[len(sizes) // 2] if sizes else 0,
               "pending_at_close": queued, "programs_in_window": rec.programs}
        if hasattr(kind, "sweep_fields"):
            row.update(kind.sweep_fields(rec))
        print(json.dumps(row), flush=True)
    dep.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
