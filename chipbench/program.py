"""The program under test, as the benchmark drives it: its configuration
type, ``ServeEngine`` and ``Request``.  Nothing else of the program is
used, apart from the engine's calls that the traced run annotates
(``loop.py``)."""

from __future__ import annotations

import dataclasses
from typing import Any
from typing import Dict
from typing import Sequence

import jax
import numpy as np

from repro.configs import get_arch
from repro.serve import Request
from repro.serve import ServeEngine
from repro.serve import engine as engine_module


def arch(base: str, fields: Dict[str, Any]):
    """``get_arch(base)`` with ``fields`` replaced; a dict replaces the
    fields of a nested group."""
    cfg = get_arch(base)
    kw = {}
    for k, v in fields.items():
        cur = getattr(cfg, k)
        if isinstance(v, dict) and dataclasses.is_dataclass(cur):
            v = dataclasses.replace(cur, **v)
        kw[k] = v
    return dataclasses.replace(cfg, **kw)


def make_engine(cfg, params, max_batch: int, max_seq: int) -> ServeEngine:
    return ServeEngine(cfg, params, max_batch=max_batch, max_seq=max_seq)


def request(p) -> Request:
    return Request(uid=p.uid, prompt=p.prompt, max_new_tokens=p.max_new)


def warm_up(engine: ServeEngine, prompt_lens: Sequence[int]) -> None:
    """Serve one three-token request per slot, prompt lengths cycling over
    the cell's buckets: every prefill length, its splice, the decode
    program, the merge and the per-slot sampling run once."""
    lens = list(prompt_lens)
    for i in range(engine.max_batch):
        engine.add_request(Request(uid=-1 - i,
                                   prompt=np.zeros(lens[i % len(lens)],
                                                   np.int32),
                                   max_new_tokens=3))
    engine.run_to_completion()
    jax.block_until_ready(engine.cache)


__all__ = ["arch", "engine_module", "make_engine", "request", "warm_up"]
