"""Serving driver: batched requests through the ServeEngine.

Example (CPU-sized model):
    PYTHONPATH=src python -m repro.launch.serve --arch gemma-7b --reduce \
        --requests 6 --max-new 16

``--no-reduce`` (the default) serves the published widths, which needs an
accelerator that holds the whole model.
"""

from __future__ import annotations

import argparse
from dataclasses import dataclass
import functools
import time
from typing import List
from typing import Sequence

import jax
import numpy as np

from repro.configs import get_arch
from repro.configs import reduce_for_smoke
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.serve import Request
from repro.serve import ServeEngine


@dataclass(frozen=True)
class ServeStats:
    warmup_s: float      # compiling (and first running) every program used
    run_s: float         # the timed run, which compiles nothing
    steps: int           # ServeEngine.step calls in the timed run
    tokens: int          # tokens generated in the timed run


def make_requests(vocab: int, prompt_lens: Sequence[int], *, max_new: int,
                  seed: int = 0) -> List[Request]:
    """One request per entry of ``prompt_lens``, prompts drawn from
    ``seed``."""
    rng = np.random.default_rng(seed)
    return [Request(uid=i, prompt=rng.integers(2, vocab, size=int(n))
                    .astype(np.int32), max_new_tokens=max_new)
            for i, n in enumerate(prompt_lens)]


def init_params_on_device(cfg, seed: int = 0):
    """Random weights built by one jitted program on the default device, so
    no float32 intermediate of a whole weight stack is materialized."""
    return jax.jit(functools.partial(init_params, cfg))(jax.random.key(seed))


def serve(engine: ServeEngine, reqs: Sequence[Request]) -> ServeStats:
    """Serve ``reqs`` to completion after a warm-up.

    Prefill is compiled per prompt length, so the warm-up serves one
    two-token request (one prefill, one decode) per distinct length; the
    timed run then reuses only compiled programs.
    """
    t0 = time.perf_counter()
    for i, n in enumerate(sorted({len(r.prompt) for r in reqs})):
        engine.add_request(Request(uid=-1 - i, prompt=np.zeros(n, np.int32),
                                   max_new_tokens=2))
    engine.run_to_completion()
    jax.block_until_ready(engine.cache)
    t1 = time.perf_counter()
    for r in reqs:
        engine.add_request(r)
    steps = engine.run_to_completion()
    jax.block_until_ready(engine.cache)
    t2 = time.perf_counter()
    return ServeStats(warmup_s=t1 - t0, run_s=t2 - t1, steps=steps,
                      tokens=sum(len(r.tokens_out) for r in reqs))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--reduce", action=argparse.BooleanOptionalAction,
                    default=False, help="smoke-reduced config (CPU-sized)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=2)
    ap.add_argument("--max-seq", type=int, default=128)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_arch(args.arch)
    if args.reduce:
        cfg = reduce_for_smoke(cfg)
    params = init_params_on_device(cfg)
    engine = ServeEngine(cfg, params, max_batch=args.max_batch,
                         max_seq=args.max_seq)
    lens = np.random.default_rng(0).integers(4, 24, size=args.requests)
    reqs = make_requests(cfg.vocab, lens, max_new=args.max_new)
    stats = serve(engine, reqs)
    for r in reqs:
        print(f"req {r.uid}: prompt_len={len(r.prompt)} -> {r.tokens_out}")
    print(f"{args.requests} requests, {stats.tokens} tokens: warm-up "
          f"{stats.warmup_s:.2f}s, then {stats.run_s:.2f}s "
          f"({stats.tokens / stats.run_s:.1f} tok/s, {stats.steps} engine "
          f"steps, slot reuse via dead-block retirement)")


if __name__ == "__main__":
    main()
