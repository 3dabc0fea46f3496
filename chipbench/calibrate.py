"""Readings that set a cell's limit: on each seed, one run of the cell as
the benchmark runs it, plus the control (the reference in float8 in the
program's place) read on the same compared tokens.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --seconds <s>

All seeds run in one process; each prints the run's ``chipbench`` line,
with ``control_numbers`` and ``control_correct`` (the harness's verdict on
the control's numbers under the configuration's limits), and its result.
Run it on the chip; the benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path
import sys
import time

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench.harness import run_cell
    for seed in (int(s) for s in args.seeds.split(",")):
        r = run_cell(ROOT, args.workload, seed, args.seconds, False,
                     time.perf_counter(), control=True)
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
