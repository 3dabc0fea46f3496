"""Runs of a cell with the timed path broken underneath, which have to come
out not correct:

- ``state_unchanged``: a decode that returns its cache or state unchanged;
- ``half_batch``: the upper half of the batch decoded from the lower
  half's logits;
- ``token_altered``: the first token altered where prefill produces it.

    python3 chipbench/faults.py --workload <cell> --fault <name> \
        --seeds 1,2,3 --seconds <s>

All seeds run in one process; each prints the run's ``chipbench`` line and
its result.  Run it on the chip to read a fault at the cell's own size; the
benchmark's own runs never plant one.  (A one-chip cell has no exchange
between chips to leave out.)
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path
import sys
import time
import types

ROOT = Path(__file__).resolve().parents[1]
FAULTS = ("state_unchanged", "half_batch", "token_altered")


def broken(fault: str):
    """The program's adapter module (``program.py``) with ``fault``
    planted in the engine it makes."""
    from chipbench import program
    import jax.numpy as jnp

    def make_engine(cfg, params, max_batch, max_seq):
        eng = program.make_engine(cfg, params, max_batch, max_seq)
        decode, prefill = eng._decode, eng._prefill
        if fault == "state_unchanged":
            def unchanged(p, t, c):
                return decode(p, t, c)[0], c
            eng._decode = unchanged
        elif fault == "half_batch":
            def half(p, t, c):
                logits, cache = decode(p, t, c)
                h = logits.shape[0] // 2
                return logits.at[h:].set(logits[:logits.shape[0] - h]), cache
            eng._decode = half
        elif fault == "token_altered":
            def shifted(p, t):
                logits, cache = prefill(p, t)
                return jnp.roll(logits, 1, axis=-1), cache
            eng._prefill = shifted
        else:
            raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
        return eng
    return types.SimpleNamespace(
        arch=program.arch, request=program.request,
        warm_up=program.warm_up, engine_module=program.engine_module,
        make_engine=make_engine)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True, choices=FAULTS)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench.harness import run_cell
    for seed in (int(s) for s in args.seeds.split(",")):
        r = run_cell(ROOT, args.workload, seed, args.seconds, False,
                     time.perf_counter(), make_program=broken(args.fault))
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
