"""Whole runs of tiny cells on the CPU (the chip check skipped): the result
line, the per-layer readers, and cells, traffic and metrics added as new
files alone."""

from __future__ import annotations

import json

from chipbench import spec
from chipbench import traffic
import pytest


@pytest.mark.parametrize("cell", ["td", "ts"])
def test_tiny_cell_runs_and_is_correct(run_tiny, bench_root, cell):
    r = run_tiny(cell)
    assert set(r) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(r)[-1] == "check"
    assert r["correct"] is True and r["failed"] == 0
    bench = json.loads((bench_root / "BENCHMARK.json").read_text())
    mix = [w["traffic"] for w in bench["workloads"] if w["name"] == cell][0]
    assert r["attempted"] == len(traffic.schedule(
        spec.load_traffic(bench_root, mix), 2.0))
    assert set(r["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["check"]["logit_gap"]["value"] <= \
        r["check"]["logit_gap"]["limit"]


def test_traced_run_reports_host_side_layer_metrics(run_tiny):
    r = run_tiny("td", trace=True)
    assert r["correct"] is True
    # the CPU has no device trace: device-trace readers find nothing and
    # are left out; the host-clock and counter readers report
    assert {"queue_wait_ms_p50", "decode_calls_per_step", "mfu.decode",
            "mfu.prefill"} <= set(r["metrics"])
    assert "decode_roofline" not in r["metrics"]
    assert r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_new_config_traffic_and_metric_need_no_edit(bench_root, run_tiny):
    d = bench_root / "chipbench"
    cfg = json.loads((d / "configs" / "tiny-dense.json").read_text())
    cfg["name"] = "tiny-dense-wide"
    cfg["config"]["intermediate_size"] = 192
    (d / "configs" / "tiny-dense-wide.json").write_text(json.dumps(cfg))
    mix = json.loads((d / "traffic" / "tiny.json").read_text())
    mix["arrivals"]["rate"] = 3.0
    (d / "traffic" / "tiny-slow.json").write_text(json.dumps(mix))
    (d / "metrics" / "served_requests.py").write_text(
        "def read(ctx):\n    return float(len(ctx.log.served))\n")
    bench = json.loads((bench_root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "new-cell", "config":
                               "tiny-dense-wide", "traffic": "tiny-slow",
                               "chips": 1, "why": "added as files alone"})
    bench["per_layer"].append({"name": "served_requests", "unit": "requests",
                               "better": "higher", "source": "host_clock",
                               "layer": "scheduler", "moves": "ttft_p90_ms",
                               "workloads": ["new-cell"]})
    (bench_root / "BENCHMARK.json").write_text(json.dumps(bench))
    r = run_tiny("new-cell", trace=True)
    assert r["correct"] is True
    assert r["metrics"]["served_requests"]["value"] == 6.0


def test_control_fails_where_the_program_passes(run_tiny, capsys):
    """The reference in float8 in the program's place reads a gap beyond
    the limit that the bfloat16 program keeps under, and the harness's
    own verdict on it is not correct."""
    for cell in ("td", "ts"):
        r = run_tiny(cell, control=True)
        info = [ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("chipbench ")][-1]
        info = json.loads(info[len("chipbench "):])
        limit = r["check"]["logit_gap"]["limit"]
        assert r["correct"] is True
        assert r["check"]["logit_gap"]["value"] <= limit
        assert info["control_numbers"]["logit_gap"] > limit
        assert info["control_correct"] is False
