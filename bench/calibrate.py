"""Readings for the limits of ``correct``: run a cell on several seeds in
one process and, beside each run's own comparison with the reference,
compare the control that the cell's kind defines with the reference on
the same served batches (for ``oms_search``, the reference on half of
every hypervector; ``bench/kinds/<kind>.py``, ``checks``).

    python3 bench/calibrate.py --workload hek293.oms.offline \
        --seeds 1,2,3 --seconds 5

The benchmark's own runs never do this.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", required=True)
    args = ap.parse_args(argv)
    rc = 0
    for seed in args.seeds.split(","):
        rc |= run.main(["--workload", args.workload, "--seed", seed,
                        "--seconds", args.seconds, "--trace", "0"],
                       control=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
