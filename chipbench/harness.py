"""One run of one cell: set-up, the measured window, the per-layer readers
(traced run), and the comparison that decides ``correct``."""

from __future__ import annotations

from dataclasses import dataclass
import json
from pathlib import Path
import shutil
import sys
import tempfile
import time
from typing import Any
from typing import Dict
from typing import List
from typing import Optional

from chipbench import check
from chipbench import loop
from chipbench import peaks
from chipbench import spec
from chipbench import stats
from chipbench import traffic as traffic_mod
from chipbench import weights
from chipbench import xtrace
import jax
import numpy as np

CACHE_DIR = Path(".cache") / "chipbench-jax"     # inside the checkout
TOP = 10


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Context:
    """What a per-layer reader reads."""
    log: loop.Log
    red: Optional[xtrace.Reduced]
    config: Dict[str, Any]
    work: Any
    peak: peaks.Peak
    memory_peak: int


class _Compiles:
    """Counts programs lowered and compiled by XLA while it is on."""

    def __init__(self):
        self.lowered = 0
        self.compiled = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1
            self.compile_s += duration

    def snapshot(self):
        return self.lowered, self.compiled, self.compile_s


def enable_cache(root: Path) -> None:
    jax.config.update("jax_compilation_cache_dir", str(root / CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips(n: int) -> List:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips; JAX found {len(devs)}")
    return devs


def _free_device_memory() -> None:
    for a in jax.live_arrays():
        a.delete()


@dataclass
class Cell:
    """A cell's files, found by name, and the chips it runs on."""
    bench: Dict[str, Any]
    cfile: Dict[str, Any]
    c: Dict[str, Any]               # the configuration as run
    mix: Dict[str, Any]
    ref: Any                        # refs.<family>
    work: Any                       # work.<family>
    devs: List
    peak: peaks.Peak


def load_cell(root: Path, name: str) -> Cell:
    """Finds the cell's files, checks its chips and turns the persistent
    compile cache on."""
    bench = spec.load_benchmark(root)
    cell = spec.workload(bench, name)
    cfile = spec.load_config(root, cell["config"])
    devs = chips(cell["chips"])
    enable_cache(root)
    return Cell(bench, cfile, spec.as_run(cfile),
                spec.load_traffic(root, cell["traffic"]),
                spec.family_module("refs", cfile["family"]),
                spec.family_module("work", cfile["family"]),
                devs, peaks.peak_for(devs[0].device_kind))


def program_weights(cell: Cell, prog, seed: int):
    """The program's configuration and its weights, made on the device
    from ``seed``."""
    cfg = prog.arch(cell.cfile["base_arch"], cell.ref.arch_fields(cell.c))
    params = weights.program_params(jax.random.key(seed),
                                    cell.ref.leaves(cell.c),
                                    cell.ref.n_layers(cell.c),
                                    cell.ref.finish)
    return cfg, params


def warm_engine(cell: Cell, prog, cfg, params):
    """The engine, with every program that the cell's traffic uses run
    once."""
    engine = prog.make_engine(cfg, params, **cell.cfile["engine"])
    prog.warm_up(engine, traffic_mod.prompt_buckets(cell.mix))
    return engine


def run_cell(root: Path, name: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, make_program=None,
             control: bool = False) -> Dict[str, Any]:
    """One run; returns the result line.  ``make_program`` (tests and
    ``faults.py``) replaces the adapter module of the program under test.
    ``control`` (``calibrate.py`` only) also reads the control's numbers
    on the same sample, and its verdict, on the ``chipbench`` line."""
    compiles = _Compiles()
    cell = load_cell(root, name)
    bench, cfile, c, mix = cell.bench, cell.cfile, cell.c, cell.mix
    ref, work, devs, peak = cell.ref, cell.work, cell.devs, cell.peak
    dev = devs[0]
    if make_program is None:
        from chipbench import program as make_program

    # ---- set-up: weights on the device, the engine, its programs warm
    phases = {"start": time.perf_counter() - t_start}
    cfg, params = program_weights(cell, make_program, seed)
    jax.block_until_ready(params)
    phases["weights"] = time.perf_counter() - t_start
    engine = warm_engine(cell, make_program, cfg, params)
    phases["warm_up"] = time.perf_counter() - t_start
    planned = traffic_mod.generate(mix, seconds, seed, ref.vocab(c))
    setup_counts = compiles.snapshot()
    setup_s = time.perf_counter() - t_start

    # ---- the window
    trace_dir = Path(tempfile.mkdtemp(prefix="chipbench-trace-")) \
        if trace else None
    win_span = None
    if trace:
        jax.profiler.start_trace(
            str(trace_dir), profiler_options=_profile_options())
        win_span = jax.profiler.TraceAnnotation(loop.SPAN + "window")
        win_span.__enter__()

    def close():
        if trace:
            win_span.__exit__(None, None, None)
            jax.profiler.stop_trace()

    log = loop.run_open_loop(engine, planned, make_program.request, seconds,
                             spans=trace,
                             engine_module=make_program.engine_module,
                             on_close=close)
    in_window = [a - b for a, b in zip(compiles.snapshot(), setup_counts)]
    memory_peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    e2e = stats.end_to_end(log)
    e2e["setup_s"] = setup_s

    red = None
    if trace:
        red = xtrace.reduce(xtrace.load(xtrace.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)

    # ---- the comparison, after the program's state is freed
    n_failed = stats.failed(log)
    sample = check.pick(log.served, seed, **cfile["check"]["sample"])
    served_finished = [s for s in log.served if s.req.done]
    del engine, params
    _free_device_memory()
    key = jax.random.key(seed)
    numbers: Dict[str, Optional[float]] = {"failed_requests": n_failed}
    control_numbers = None
    if sample:
        seqs, rows = [p.seq for p in sample], [p.rows for p in sample]
        ref_logits = ref.logits(c, key, seqs, rows)
        numbers.update(check.served_numbers(ref_logits, sample))
        if control:
            control_numbers = check.control_numbers(
                ref_logits, ref.logits(c, key, seqs, rows, fp8=True))
    limits = {"failed_requests": 0, **cfile["check"]["limits"]}
    correct = check.verdict(numbers, limits)
    control_correct = None
    if control_numbers is not None:
        control_correct = check.verdict(dict(numbers, **control_numbers),
                                        limits)

    # ---- the result
    result: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": len(log.served),
        "failed": n_failed,
    }
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    if trace:
        ctx = Context(log, red, c, work, peak, memory_peak)
        metrics = {}
        for m in spec.per_layer_metrics(bench, name):
            v = spec.metric_reader(root, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = red.busy_ns * 1e-9
        device["window_s"] = red.window.dur * 1e-9
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = _breakdown(red)
    else:
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in spec.end_to_end_metrics(bench, name)
            if e2e.get(m["name"]) is not None}
        result["device"] = device
    result["check"] = {k: {"value": numbers.get(k), "limit": limits[k]}
                      for k in limits}

    # ---- earlier lines: what a reader of the run needs to trust it
    lateness = [s.added - s.due for s in log.served]
    info = {
        "requests_due": len(log.served),
        "requests_finished": len(served_finished),
        "tokens_in_window": stats.tokens_in_window(log),
        "steps": len(log.steps),
        "longest_step_ms": max((st.end - st.start for st in log.steps
                                if st.start < log.t_end), default=0.0) * 1e3,
        "generator_lateness_ms_p50_max": [
            float(np.median(lateness)) * 1e3 if lateness else 0.0,
            max(lateness, default=0.0) * 1e3],
        "drain_s": log.t_drained - log.t_end,
        "compiles_in_window": {"lowered": in_window[0],
                               "xla_compiled": in_window[1]},
        "setup_phases_end_s": phases,
        "setup_compiles": {"lowered": setup_counts[0],
                           "xla_compiled": setup_counts[1],
                           "xla_compile_s": setup_counts[2]},
        "compared": {"requests": len(sample),
                     "served_tokens": int(sum(len(p.served)
                                              for p in sample))},
        "end_to_end": e2e,
        "compared_numbers": numbers,
    }
    if control:
        info["control_numbers"] = control_numbers
        info["control_correct"] = control_correct
    if red is not None:
        steps_s = sum(s.dur for s in red.steps) * 1e-9
        info["idle_share_window_pct"] = 100 * (1 - red.busy_ns
                                               / red.window.dur)
        info["idle_share_pending_pct"] = (
            100 * (1 - red.busy_within(red.steps) * 1e-9 / steps_s)
            if steps_s else None)
    print("chipbench " + json.dumps(info), flush=True)
    for k in limits:
        print(f"check {k} value={numbers.get(k)} limit={limits[k]}",
              file=sys.stderr, flush=True)
    return result


def _profile_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def _breakdown(red: xtrace.Reduced) -> Dict[str, List]:
    ops = sorted(xtrace.device_ops(red).items(), key=lambda kv: -kv[1])
    gaps = sorted(xtrace.idle_gaps(red), key=lambda g: g[0] - g[1])[:TOP]
    return {"device_ops": [[k, v] for k, v in ops[:TOP]],
            "idle_gaps": [[xtrace.host_label(red, (lo + hi) / 2),
                           (hi - lo) * 1e-9] for lo, hi in gaps]}
