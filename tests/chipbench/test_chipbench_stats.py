"""End-to-end metric arithmetic on synthetic driver logs."""

from __future__ import annotations

from chipbench import stats
from chipbench.loop import Log
from chipbench.loop import Served
import numpy as np
import pytest


class _Req:
    def __init__(self, done=True):
        self.done = done


def _served(uid, due, times, done=True):
    return Served(uid=uid, due=due, prompt_len=8, req=_Req(done),
                  times=list(times))


def _log(served, seconds=10.0, t_end=None, t_drained=None):
    log = Log(t0=0.0, seconds=seconds, served=served, steps=[])
    log.t_end = seconds if t_end is None else t_end
    log.t_drained = log.t_end if t_drained is None else t_drained
    return log


def test_ttft_counts_every_request_due_in_the_window():
    served = [_served(i, due=float(i), times=[i + 0.1 * (i + 1)])
              for i in range(9)]
    # the tenth never got a first token: it counts with its wait until the
    # drain ended, and it is a failure
    served.append(_served(9, due=9.0, times=[], done=False))
    log = _log(served, t_drained=14.0)
    ttft = stats.ttft_s(log)
    assert len(ttft) == 10
    assert ttft[-1] == pytest.approx(5.0)
    assert stats.failed(log) == 1
    m = stats.end_to_end(log)
    assert m["ttft_p90_ms"] == pytest.approx(
        np.percentile(ttft, 90) * 1e3)
    assert m["ttft_p90_ms"] > 900.0     # the failure is in the tail


def test_gaps_close_inside_the_window_only():
    s = _served(0, due=0.0, times=[1.0, 1.5, 2.5, 9.0, 11.0, 12.0])
    log = _log([s], seconds=10.0)
    assert stats.gaps_s(log) == pytest.approx([0.5, 1.0, 6.5])
    m = stats.end_to_end(log)
    assert m["itl_p50_ms"] == pytest.approx(1000.0)


def test_gaps_pool_all_requests_for_the_tails():
    a = _served(0, 0.0, [1.0, 1.1, 1.2, 1.3])
    b = _served(1, 0.0, [2.0, 3.0])
    m = stats.end_to_end(_log([a, b]))
    gaps = [0.1, 0.1, 0.1, 1.0]
    assert m["itl_p50_ms"] == pytest.approx(np.percentile(gaps, 50) * 1e3)
    assert m["itl_p95_ms"] == pytest.approx(np.percentile(gaps, 95) * 1e3)


def test_rate_is_over_the_whole_window():
    # 6 tokens inside a 10 s window, 2 after it: 0.6 tokens/s, however
    # short the stretch in which they came
    a = _served(0, 0.0, [4.0, 4.1, 4.2, 4.3, 4.4, 4.5, 10.5, 11.0])
    log = _log([a], seconds=10.0)
    assert stats.tokens_in_window(log) == 6
    assert stats.end_to_end(log)["output_tok_s"] == pytest.approx(0.6)


def test_empty_window_reports_no_latency():
    m = stats.end_to_end(_log([]))
    assert m["ttft_p90_ms"] is None and m["itl_p50_ms"] is None
    assert m["output_tok_s"] == 0.0
