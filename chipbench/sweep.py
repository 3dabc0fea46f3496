"""Find a cell's knee: the highest offered rate with no growing backlog.

    python3 chipbench/sweep.py --workload <cell> --seed <n> \
        --seconds <s> --rates 0.2,0.3,0.4

Builds the cell's engine once per rate (its programs come from the
persistent cache after the first) on weights made once, offers the cell's
traffic at each rate for ``--seconds``, and prints one JSON line per rate:
requests offered and finished, output tokens per second, time to first
token of the first and last third of the requests, and the mean number of
requests waiting for a first token in the first and last third of the
window.  A backlog that grows shows as the last third waiting longer and
in greater numbers.  Run it on the chip; it is not part of a benchmark
run.
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path
import sys

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def _waiting(log, t):
    return sum(1 for s in log.served
               if s.due <= t and (not s.times or s.times[0] > t))


def summarize(log, rate):
    from chipbench import stats
    n = len(log.served)
    third = max(1, n // 3)
    ttft = stats.ttft_s(log)
    grid = np.linspace(log.t0, log.t_end, 60)
    wait = [_waiting(log, t) for t in grid]
    return {"rate": rate, "offered": n,
            "finished": sum(1 for s in log.served if s.req.done),
            "output_tok_s": stats.tokens_in_window(log) / log.seconds,
            "ttft_s_first_third": float(np.median(ttft[:third])),
            "ttft_s_last_third": float(np.median(ttft[-third:])),
            "waiting_first_third": float(np.mean(wait[:20])),
            "waiting_last_third": float(np.mean(wait[-20:])),
            "decode_steps": len(log.steps)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness
    from chipbench import loop
    from chipbench import program
    from chipbench import traffic
    cell = harness.load_cell(ROOT, args.workload)
    cfg, params = harness.program_weights(cell, program, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        engine = harness.warm_engine(cell, program, cfg, params)
        at = dict(cell.mix, arrivals=dict(cell.mix["arrivals"], rate=rate))
        planned = traffic.generate(at, args.seconds, args.seed,
                                   cell.ref.vocab(cell.c))
        log = loop.run_open_loop(engine, planned, program.request,
                                 args.seconds, drain_s=0.0)
        print(json.dumps(summarize(log, rate)), flush=True)
        del engine
    return 0


if __name__ == "__main__":
    sys.exit(main())
