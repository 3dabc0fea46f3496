"""End-to-end metrics from the driver's log: every request due in the
window counts toward the time to first token, every gap between two
tokens of one request that closes inside the window counts toward the
inter-token gaps, and a rate is taken over the whole window."""

from __future__ import annotations

from typing import Dict
from typing import List

from chipbench.loop import Log
import numpy as np


def percentile(values: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, dtype=float), q))


def ttft_s(log: Log) -> List[float]:
    """One per request due in the window; a request still without a first
    token when the drain ended counts with its wait until then."""
    return [(s.times[0] if s.times else log.t_drained) - s.due
            for s in log.served]


def gaps_s(log: Log) -> List[float]:
    out = []
    for s in log.served:
        t = np.asarray(s.times)
        g = np.diff(t)
        out.extend(g[t[1:] <= log.t_end].tolist())
    return out


def tokens_in_window(log: Log) -> int:
    return sum(int(np.sum(np.asarray(s.times) <= log.t_end))
               for s in log.served)


def end_to_end(log: Log) -> Dict[str, float]:
    ttft = ttft_s(log)
    gaps = gaps_s(log)
    return {
        "ttft_p50_ms": percentile(ttft, 50) * 1e3 if ttft else None,
        "ttft_p90_ms": percentile(ttft, 90) * 1e3 if ttft else None,
        "itl_p50_ms": percentile(gaps, 50) * 1e3 if gaps else None,
        "itl_p95_ms": percentile(gaps, 95) * 1e3 if gaps else None,
        "output_tok_s": tokens_in_window(log) / log.seconds,
    }


def failed(log: Log) -> int:
    """Requests due in the window that never returned a first token."""
    return sum(1 for s in log.served if not s.times)
