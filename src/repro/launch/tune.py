"""ERT-style empirical autotuner CLI.

Measures this device's real compute and memory-bandwidth ceilings
(growing-matmul and growing-copy sweeps, :mod:`repro.tune.microbench`),
sweeps the Pallas kernel block sizes against representative workloads
(:mod:`repro.tune.sweep`), and persists the winning configs in a JSON
tuning table keyed by (device kind, shape bucket). Point
``REPRO_TUNING_TABLE`` at the written file and every kernel ops layer —
and every serving path built on them — resolves its tile sizes from the
table at trace time, falling back to the hand-tuned defaults for shapes
(or device kinds) the table doesn't cover. ``repro.launch.dryrun``
prices its roofline terms with the measured ceilings whenever such a
table is active.

Usage:
  PYTHONPATH=src python -m repro.launch.tune --out artifacts/tuning_table.json
  PYTHONPATH=src python -m repro.launch.tune --quick --ops topk_hamming,imc_mvm
"""

from __future__ import annotations

import argparse
import json

from repro.launch.compile_cache import enable_compile_cache
from repro.tune import ENV_VAR
from repro.tune.sweep import OPS, build_tuning_table, tuned_vs_default_ratio


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="artifacts/tuning_table.json",
                    help="tuning-table JSON path (written atomically)")
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweep for CI / CPU smoke runs")
    ap.add_argument("--ops", default=None,
                    help=f"comma-separated subset of {','.join(OPS)}")
    ap.add_argument("--iters", type=int, default=3,
                    help="timing iterations per candidate (median taken)")
    ap.add_argument("--skip-ceilings", action="store_true",
                    help="sweep blocks only; keep the table ceiling-free")
    args = ap.parse_args(argv)
    enable_compile_cache()

    ops = None
    if args.ops:
        ops = tuple(s.strip() for s in args.ops.split(",") if s.strip())
        unknown = [o for o in ops if o not in OPS]
        if unknown:
            ap.error(f"unknown ops {unknown}; choose from {OPS}")

    table = build_tuning_table(args.out, quick=args.quick, ops=ops,
                               iters=args.iters,
                               skip_ceilings=args.skip_ceilings)

    print(f"device_kind: {table.device_kind}")
    if table.ceilings:
        print("ceilings: peak %.2f GFLOP/s, hbm %.2f GB/s"
              % (table.ceilings["peak_flops"] / 1e9,
                 table.ceilings["hbm_bw"] / 1e9))
    for op, buckets in table.ops.items():
        for bucket, entry in buckets.items():
            us, dus = entry.get("us"), entry.get("default_us")
            speedup = f" ({dus / us:.2f}x vs default)" if us and dus else ""
            print(f"  {op} [{bucket}]: {json.dumps(entry['blocks'])}"
                  f"{speedup}")
    print("worst tuned-vs-default ratio: %.3f"
          % tuned_vs_default_ratio(table))
    print(f"wrote {args.out}; activate with {ENV_VAR}={args.out}")
    return table


if __name__ == "__main__":
    main()
