"""One general generator for every traffic file under ``traffic/``.

A traffic file gives the arrival process, the prompt-length buckets and
the output-length distribution.  The schedule (when each request is due,
and its prompt and output lengths) is drawn from the file's own
``schedule_seed``, so every run of a cell offers the same work at the same
times; the run's ``--seed`` draws the prompt tokens.  Sizes and gaps are
stratified quantiles of their distributions, shuffled, so a window of n
requests holds the distribution's shape and not one random draw of it.

Arrival processes (the seeded Poisson and geometric-burst processes of
``repro.serve.traffic``, in seconds and tokens):

- ``poisson``: ``round(rate * seconds)`` requests, exponential gaps;
- ``bursts``: bursts of geometric size with mean ``burst_mean``, all
  members due at once, exponential gaps between bursts, ``rate`` requests
  per second on average.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any
from typing import Dict
from typing import List
from typing import NamedTuple

import numpy as np


class Planned(NamedTuple):
    uid: int
    due_s: float            # seconds after the window opens
    prompt: np.ndarray      # int32 token ids
    max_new: int


def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _exp_quantiles(n: int, rng: np.random.Generator) -> np.ndarray:
    return rng.permutation(-np.log1p(-_strata(n)))


def _due_times(arr: Dict[str, Any], seconds: float,
               rng: np.random.Generator) -> np.ndarray:
    """Due times in [0, seconds), one per request."""
    n = max(1, round(arr["rate"] * seconds))
    if arr["process"] == "poisson":
        sizes = np.ones(n, dtype=int)
    elif arr["process"] == "bursts":
        p = 1.0 / arr["burst_mean"]
        m = max(1, round(n / arr["burst_mean"]))
        sizes = np.ceil(np.log1p(-_strata(m)) / math.log1p(-p)).astype(int)
        sizes = rng.permutation(np.maximum(sizes, 1))
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    gaps = _exp_quantiles(len(sizes), rng)
    # the first event half a gap in, the last inside the window
    events = (np.cumsum(gaps) - gaps[0] / 2) * (seconds / gaps.sum())
    return np.repeat(events, sizes)


def _prompt_lens(spec: Dict[str, Any], n: int,
                 rng: np.random.Generator) -> np.ndarray:
    values = np.asarray(spec["values"])
    w = np.asarray(spec["weights"], dtype=float)
    cdf = np.cumsum(w / w.sum())
    idx = np.searchsorted(cdf, rng.permutation(_strata(n)), side="right")
    return values[np.minimum(idx, len(values) - 1)]


def _output_lens(spec: Dict[str, Any], n: int,
                 rng: np.random.Generator) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown output distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf(u) for u in rng.permutation(
        _strata(n))])
    lens = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(lens, spec["min"], spec["max"]).astype(int)


def schedule(traffic: Dict[str, Any], seconds: float) -> List[tuple]:
    """(due_s, prompt_len, max_new) per request, sorted by due time."""
    rng = np.random.default_rng(traffic["schedule_seed"])
    due = _due_times(traffic["arrivals"], seconds, rng)
    plen = _prompt_lens(traffic["prompt_len"], len(due), rng)
    out = _output_lens(traffic["output_len"], len(due), rng)
    return [(float(d), int(p), int(o)) for d, p, o in zip(due, plen, out)]


def generate(traffic: Dict[str, Any], seconds: float, seed: int,
             vocab: int) -> List[Planned]:
    """The requests of one run: the cell's schedule, with prompt tokens
    drawn from ``seed`` (ids 2..vocab-1, leaving the usual BOS/EOS ids
    out)."""
    rng = np.random.default_rng(seed)
    return [Planned(uid=i, due_s=d,
                    prompt=rng.integers(2, vocab, size=p, dtype=np.int32),
                    max_new=o)
            for i, (d, p, o) in enumerate(schedule(traffic, seconds))]


def prompt_buckets(traffic: Dict[str, Any]) -> List[int]:
    return sorted(int(v) for v in traffic["prompt_len"]["values"])
