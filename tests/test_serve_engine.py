"""``ServeEngine`` decodes every active slot in one call per step, each
slot at its own position, and serves the same tokens as decoding each
group of slots at one position separately and merging the rows it
updated."""

from __future__ import annotations

from typing import Dict
from typing import List

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

# distinct positions, and slots one step apart (8 and 9 admitted together)
LENS = (8, 9, 16, 12, 9, 8, 16)
MAX_NEW = (6, 3, 5, 7, 4, 6, 3)


class GroupedEngine(ServeEngine):
    """The reference: one scalar-position decode per group of slots at one
    position, each followed by a merge of that group's rows."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.groups_per_step: List[int] = []

    def _decode_active(self, active: List[int]) -> None:
        toks = np.zeros((self.max_batch, 1), dtype=np.int32)
        for i in active:
            toks[i, 0] = self.sched.slots[i].tokens_out[-1]
        groups: Dict[int, List[int]] = {}
        for i in active:
            groups.setdefault(int(self.slot_pos[i]), []).append(i)
        self.groups_per_step.append(len(groups))
        for pos, slots in groups.items():
            cache = self.cache._replace(pos=jnp.asarray(pos, jnp.int32))
            logits, new_cache = self._decode(self.params, jnp.asarray(toks),
                                             cache)
            self.cache = engine_module._merge_slots(self.cache, new_cache,
                                                    slots)
            for i in slots:
                req = self.sched.slots[i]
                nxt = int(jnp.argmax(logits[i, 0]))
                req.tokens_out.append(nxt)
                self.slot_pos[i] += 1
                if len(req.tokens_out) >= req.max_new_tokens:
                    self._retire(i)


@pytest.fixture(scope="module", params=["llama3.2-3b", "mamba2-2.7b",
                                        "zamba2-7b"])
def model(request):
    cfg = reduce_for_smoke(get_arch(request.param))
    return cfg, init_params(cfg, jax.random.key(0))


def _requests(cfg):
    rng = np.random.default_rng(3)
    return [Request(uid=i, max_new_tokens=m,
                    prompt=rng.integers(0, cfg.vocab, n).astype(np.int32))
            for i, (n, m) in enumerate(zip(LENS, MAX_NEW))]


def _serve(eng, reqs) -> int:
    """Runs ``eng`` until every request finished; returns the number of
    steps that decoded."""
    for r in reqs:
        eng.add_request(r)
    decoding = 0
    for _ in range(200):
        n = eng.step()
        decoding += bool(n)
        if n == 0 and eng.sched.drained:
            return decoding
    raise AssertionError("requests left unfinished")


def test_one_call_per_step_serves_the_grouped_tokens(model, monkeypatch):
    cfg, params = model
    ref = GroupedEngine(cfg, params, max_batch=4, max_seq=64)
    want = _requests(cfg)
    _serve(ref, want)
    assert max(ref.groups_per_step) >= 3        # the case is not trivial

    merges = []
    real_merge = engine_module._merge_slots
    monkeypatch.setattr(engine_module, "_merge_slots",
                        lambda *a: merges.append(a) or real_merge(*a))
    eng = ServeEngine(cfg, params, max_batch=4, max_seq=64)
    calls = []
    real_decode = eng._decode

    def decode(*a):
        calls.append(a[2].pos)
        return real_decode(*a)
    eng._decode = decode
    got = _requests(cfg)
    decoding = _serve(eng, got)

    assert [r.tokens_out for r in got] == [r.tokens_out for r in want]
    assert all(r.done and len(r.tokens_out) == r.max_new_tokens for r in got)
    assert len(calls) == decoding and not merges
    assert all(p.shape == (4,) and p.dtype == jnp.int32 for p in calls)
    assert any(len(set(np.asarray(p).tolist())) >= 3 for p in calls)


def test_decode_compiles_once_for_every_mix_of_positions(model):
    cfg, params = model
    eng = ServeEngine(cfg, params, max_batch=4, max_seq=64)
    _serve(eng, _requests(cfg)[:4])
    compiled = eng._decode._cache_size()
    _serve(eng, _requests(cfg)[3:])
    assert eng._decode._cache_size() == compiled == 1
