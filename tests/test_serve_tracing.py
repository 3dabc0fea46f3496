"""The serving engine's tracer: spans recorded only when a tracer is
given, how the spans nest, what the step's counting attributes count,
compiles put down to the span that caused them, and the names of the
engine's device programs."""

from __future__ import annotations

import collections
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.configs import reduce_for_smoke
from repro.models import init_params
from repro.serve import Request
from repro.serve import ServeEngine
from repro.serve import engine as engine_module
from repro.serve.tracing import Tracer

LENS = (8, 16, 8, 8, 16, 8)


@pytest.fixture(scope="module", params=["llama3.2-3b", "mamba2-2.7b"])
def model(request):
    cfg = reduce_for_smoke(get_arch(request.param))
    return cfg, init_params(cfg, jax.random.key(0))


def _serve(model, tracer=None, lens=LENS, max_new=5):
    cfg, params = model
    eng = ServeEngine(cfg, params, max_batch=4, max_seq=64, tracer=tracer)
    rng = np.random.default_rng(1)
    reqs = [Request(uid=i, max_new_tokens=max_new,
                    prompt=rng.integers(0, cfg.vocab, n).astype(np.int32))
            for i, n in enumerate(lens)]
    for r in reqs:
        eng.add_request(r)
    eng.run_to_completion()
    return eng, reqs


def test_tracer_changes_no_token_and_off_records_nothing(model, tmp_path):
    _, plain = _serve(model)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng, off = _serve(model)
    finally:
        jax.profiler.stop_trace()
    with Tracer() as t:
        _, on = _serve(model, t)
    assert [r.tokens_out for r in off] == [r.tokens_out for r in plain]
    assert [r.tokens_out for r in on] == [r.tokens_out for r in plain]
    assert eng.tracer is None and not eng._waiting
    profile = jax.profiler.ProfileData.from_file(
        str(next(Path(tmp_path).glob("plugins/profile/*/*.xplane.pb"))))
    names = {e.name for plane in profile.planes for line in plane.lines
             for e in line.events}
    assert not any(n.startswith("serve.") for n in names)
    assert any(s.name == "serve.step" for s in t.spans)


def test_spans_nest_and_share_the_request_uid(model):
    with Tracer() as t:
        _, reqs = _serve(model, t)
    spans = list(t.spans)
    by_id = {s.id: s for s in spans}
    parent = {s.id: by_id[s.parent].name if s.parent is not None else None
              for s in spans}
    want = {"serve.step": None, "serve.queue": None,
            "serve.admit": "serve.step", "serve.prefill": "serve.admit",
            "serve.splice": "serve.admit", "serve.first_token": "serve.admit",
            "serve.decode": "serve.step", "serve.sample": "serve.step"}
    assert {(s.name, parent[s.id]) for s in spans} == set(want.items())
    for s in spans:
        assert s.start <= s.end
        if s.parent is not None:
            p = by_id[s.parent]
            assert p.start <= s.start and s.end <= p.end
    # every span of a request carries its uid; a step's own spans none
    for r in reqs:
        mine = collections.Counter(s.name for s in spans if s.uid == r.uid)
        assert mine == {"serve.queue": 1, "serve.admit": 1,
                        "serve.prefill": 1, "serve.splice": 1,
                        "serve.first_token": 1}
    assert all(s.uid is None for s in spans
               if s.name in ("serve.step", "serve.decode", "serve.sample"))
    admit = [s for s in spans if s.name == "serve.admit"]
    assert sorted(s.attrs["prompt_len"] for s in admit) == sorted(LENS)
    # a queue span ends where its request's admission starts
    queue = {s.uid: s for s in spans if s.name == "serve.queue"}
    for a in admit:
        assert queue[a.uid].end <= a.start


def test_counters_count_reads_groups_and_requests(model):
    with Tracer() as t:
        eng, reqs = _serve(model, t)
    spans = list(t.spans)
    steps = [s for s in spans if s.name == "serve.step"]
    decodes = [s for s in spans if s.name == "serve.decode"]
    emitted = sum(len(r.tokens_out) for r in reqs)
    assert sum(s.attrs["host_reads"] for s in steps) == emitted
    assert sum(s.attrs["groups"] for s in steps) == len(decodes)
    assert all(set(s.attrs) == {"groups", "host_reads"} for s in steps)
    assert all(r.done for r in reqs) and not eng.sched.n_active


def test_a_new_prompt_length_compiles_inside_its_prefill(model):
    cfg, params = model
    with Tracer() as t:
        eng = ServeEngine(cfg, params, max_batch=2, max_seq=64, tracer=t)
        eng.add_request(Request(uid=0, prompt=np.zeros(8, np.int32),
                                max_new_tokens=3))
        eng.run_to_completion()
        warm = t.compiles
        eng.add_request(Request(uid=1, prompt=np.zeros(8, np.int32),
                                max_new_tokens=3))
        eng.run_to_completion()
        assert t.compiles == warm
        eng.add_request(Request(uid=2, prompt=np.zeros(12, np.int32),
                                max_new_tokens=3))
        eng.run_to_completion()
    assert t.compiles > warm
    prefill = {s.uid: s for s in t.spans if s.name == "serve.prefill"}
    assert prefill[0].attrs.get("compiles", 0) >= 1
    assert prefill[2].attrs.get("compiles", 0) >= 1
    assert "compiles" not in prefill[1].attrs


def test_closed_tracer_counts_no_more_compiles():
    with Tracer() as t:
        pass
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(7))
    assert t.compiles == 0


def test_device_programs_have_stable_names(model):
    cfg, params = model
    eng = ServeEngine(cfg, params, max_batch=2, max_seq=64)
    prompt = jnp.zeros((1, 8), jnp.int32)
    _, one = eng._prefill(params, prompt)
    toks = jnp.zeros((2, 1), jnp.int32)
    lowered = {
        "serve_prefill": eng._prefill.lower(params, prompt),
        "serve_decode": eng._decode.lower(params, toks, eng.cache),
        "serve_merge_slots": engine_module.serve_merge_slots.lower(
            eng.cache, eng.cache, np.ones(2, bool)),
        "serve_splice": engine_module.serve_splice.lower(
            eng.cache, one, np.int32(1)),
    }
    for name, low in lowered.items():
        assert f"module @jit_{name} " in low.as_text()


def test_merge_and_splice_compile_once_for_every_slot(model):
    cfg, params = model
    eng = ServeEngine(cfg, params, max_batch=4, max_seq=64)
    _, one = eng._prefill(params, jnp.zeros((1, 8), jnp.int32))
    merged = engine_module._merge_slots(eng.cache, eng.cache, [0])
    spliced = engine_module._splice(eng.cache, one, 0)
    merges = engine_module.serve_merge_slots._cache_size()
    splices = engine_module.serve_splice._cache_size()
    for slots in ([1], [0, 2], [3, 1, 2]):
        merged = engine_module._merge_slots(merged, eng.cache, slots)
    for slot in (1, 2, 3):
        spliced = engine_module._splice(spliced, one, slot)
    assert engine_module.serve_merge_slots._cache_size() == merges
    assert engine_module.serve_splice._cache_size() == splices
