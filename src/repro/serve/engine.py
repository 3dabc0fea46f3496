"""Batched serving engine: continuous batching over a slotted KV pool.

The DCO mapping (DESIGN.md §3): each slot's KV region is a *tensor* with
dataflow-known lifetime.  When a sequence finishes, its slot is retired
immediately and reused by the next queued request — the serving-level
dead-block prediction (paper §VI-F: "data from completed batches becomes
dead and pollutes the cache"; here the pollution is reclaimed the moment
``accCnt == nAcc``, i.e. at EOS/max-tokens).  A TMU instance tracks the
slot lifetimes so the analogy is executable, not rhetorical.

The engine is deliberately synchronous and functional: ``step()`` runs one
batched decode for every active slot (padding inactive slots), so the
whole loop jit-compiles to a single ``decode_step`` of static shape.  The
pooled cache's ``pos`` is a (B,) vector, one position per slot, so slots
at different positions share that one call and its returned cache is
kept whole.

Its device programs have stable names, so that a profiler trace finds
them: ``serve_prefill``, ``serve_decode``, ``serve_merge_slots`` and
``serve_splice``.  With a :class:`~repro.serve.tracing.Tracer` the engine
records spans (all named ``serve.*``) and counters of its work; without
one it records nothing.
"""

from __future__ import annotations

from dataclasses import dataclass
from dataclasses import field
from typing import Dict
from typing import List
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ArchConfig
from repro.core.tmu import TMU
from repro.core.tmu import TensorMeta
from repro.models import Cache
from repro.models import decode_step
from repro.models import init_cache
from repro.models import prefill

from .scheduler import ServeTruncation
from .scheduler import SlotScheduler
from .tracing import NO_SPAN
from .tracing import Span
from .tracing import Tracer


@dataclass
class Request:
    uid: int
    prompt: np.ndarray                 # (prompt_len,) int32
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    tokens_out: List[int] = field(default_factory=list)
    done: bool = False


class ServeEngine:
    """Continuous batching over ``max_batch`` cache slots.

    With ``tracer`` each :meth:`step` records a ``serve.step`` span holding
    ``serve.admit`` (``prompt_len``; children ``serve.prefill``,
    ``serve.splice``, ``serve.first_token``) for each request admitted, and
    one ``serve.decode`` and one ``serve.sample`` if any slot is active;
    each request records ``serve.queue`` from :meth:`add_request` to its
    admission.  The step's counters are attributes of its span: ``groups``
    (decode calls: 1 or 0) and ``host_reads`` (device values read by the
    host).
    """

    def __init__(self, cfg: ArchConfig, params, *, max_batch: int = 4,
                 max_seq: int = 256, greedy: bool = True,
                 tracer: Optional[Tracer] = None):
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.cache = init_cache(cfg, max_batch, max_seq)._replace(
            pos=jnp.zeros((max_batch,), jnp.int32))
        self.sched: SlotScheduler[Request] = SlotScheduler(max_batch)
        self.slot_pos = np.zeros(max_batch, dtype=np.int32)
        self.greedy = greedy
        # TMU tracking slot lifetimes (dead-block analogue)
        self._tmu = TMU(tensor_entries=max_batch * 2)
        self._slot_bytes = 1 << 20
        self.tracer = tracer
        self._host_reads = 0              # device values read by the host
        self._waiting: Dict[int, Span] = {}       # id(request) -> its wait

        def serve_decode(p, t, c):
            return decode_step(p, t, c, cfg)

        def serve_prefill(p, t):
            return prefill(p, t, cfg)

        # looked up on the instance at each call, so callers may wrap them
        self._decode = jax.jit(serve_decode)
        self._prefill = jax.jit(serve_prefill)

    # ------------------------------------------------------------------
    def add_request(self, req: Request) -> None:
        if self.tracer:
            self._waiting[id(req)] = self.tracer.start(
                "serve.queue", req.uid, nested=False)
        self.sched.add(req)

    def _admit(self) -> None:
        for slot, req in self.sched.admit():
            self._start(slot, req)

    def _start(self, slot: int, req: Request) -> None:
        t = self.tracer
        plen = req.prompt.shape[0]
        waited = self._waiting.pop(id(req), None)
        if waited is not None:
            t.finish(waited)
        with t.span("serve.admit", req.uid, prompt_len=plen) if t \
                else NO_SPAN:
            with t.span("serve.prefill") if t else NO_SPAN:
                prompt = jnp.asarray(req.prompt[None, :])
                logits, pcache = self._prefill(self.params, prompt)
            # splice this request's prefilled KV/state into the pooled cache
            with t.span("serve.splice") if t else NO_SPAN:
                self.cache = _splice(self.cache, pcache, slot)
            self.slot_pos[slot] = plen
            with t.span("serve.first_token") if t else NO_SPAN:
                first = int(jnp.argmax(logits[0])) if self.greedy else int(
                    jax.random.categorical(jax.random.key(req.uid),
                                           logits[0]))
                self._host_reads += 1
        req.tokens_out.append(first)
        self._tmu.register(TensorMeta(
            tensor_id=req.uid, base_addr=slot * self._slot_bytes,
            size_bytes=self._slot_bytes, tile_bytes=self._slot_bytes,
            n_acc=req.max_new_tokens))

    def _retire(self, slot: int) -> None:
        req = self.sched.release(slot)
        req.done = True
        self._tmu.clear(req.uid)          # slot retires → space reusable
        self.slot_pos[slot] = 0

    # ------------------------------------------------------------------
    def step(self) -> int:
        """One batched decode step; returns #active slots."""
        t = self.tracer
        reads_before = self._host_reads
        with t.span("serve.step") if t else NO_SPAN as sp:
            self._admit()
            active = self.sched.active_slots()
            if active:
                self._decode_active(active)
            if t:
                sp.attrs.update(groups=int(bool(active)),
                                host_reads=self._host_reads - reads_before)
        return len(active)

    def _decode_active(self, active: List[int]) -> None:
        """Decodes one token for every active slot in one call, each slot
        at its own position.  Inactive slots decode at position 0 too;
        what that writes into their rows is never read, because an
        admission splices the whole row."""
        t = self.tracer
        toks = np.zeros((self.max_batch, 1), dtype=np.int32)
        for i in active:
            toks[i, 0] = self.sched.slots[i].tokens_out[-1]
        with t.span("serve.decode") if t else NO_SPAN:
            # a copy: slot_pos moves on while the call may still read it
            cache = self.cache._replace(pos=jnp.array(self.slot_pos))
            logits, self.cache = self._decode(
                self.params, jnp.asarray(toks), cache)
        with t.span("serve.sample") if t else NO_SPAN:
            for i in active:
                req = self.sched.slots[i]
                nxt = int(jnp.argmax(logits[i, 0]))
                self._host_reads += 1
                req.tokens_out.append(nxt)
                self.slot_pos[i] += 1
                self._tmu.on_access(
                    i * self._slot_bytes + self._slot_bytes - 128, 0)
                exhausted = len(req.tokens_out) >= req.max_new_tokens
                if exhausted or (req.eos_id is not None
                                 and nxt == req.eos_id):
                    self._retire(i)

    def run_to_completion(self, max_steps: int = 1000) -> int:
        """Drive :meth:`step` until every request finishes; returns the
        number of steps taken.  Raises :class:`ServeTruncation` if the
        budget runs out with requests still active or queued (previously
        this exited silently, making truncated generations look
        finished)."""
        for n in range(max_steps):
            if self.step() == 0 and self.sched.drained:
                return n + 1
        if not self.sched.drained:
            raise ServeTruncation(max_steps, self.sched.n_active,
                                  self.sched.n_queued)
        return max_steps


# ---------------------------------------------------------------------------
def _splice(pool: Cache, one: Cache, slot: int) -> Cache:
    """Copy a single-sequence prefill cache into pool slot ``slot``."""
    return serve_splice(pool, one, np.int32(slot))


@jax.jit
def serve_splice(pool: Cache, one: Cache, slot) -> Cache:
    """One program per prompt length: ``slot`` is traced, so any slot
    runs the same program."""
    def put_kv(pool_a, one_a):
        if pool_a is None:
            return None
        pad = pool_a.shape[2] - one_a.shape[2]
        padded = jnp.pad(one_a, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        return jax.lax.dynamic_update_slice_in_dim(pool_a, padded, slot,
                                                   axis=1)

    def put_state(pool_a, one_a):
        if pool_a is None:
            return None
        return jax.lax.dynamic_update_slice_in_dim(pool_a, one_a, slot,
                                                   axis=1)

    return Cache(
        k=put_kv(pool.k, one.k), v=put_kv(pool.v, one.v),
        conv_x=put_state(pool.conv_x, one.conv_x),
        conv_bc=put_state(pool.conv_bc, one.conv_bc),
        ssm=put_state(pool.ssm, one.ssm),
        pos=pool.pos)


def _merge_slots(old: Cache, new: Cache, slots: List[int]) -> Cache:
    """Keep updated cache rows only for ``slots`` (batch axis 1).
    :meth:`ServeEngine.step` does not merge; this is a utility."""
    sel = np.zeros(old.k.shape[1] if old.k is not None
                   else old.ssm.shape[1], dtype=bool)
    sel[slots] = True
    return serve_merge_slots(old, new, sel)


@jax.jit
def serve_merge_slots(old: Cache, new: Cache, mask) -> Cache:
    """One program for every set of slots: ``mask`` (batch,) is traced."""
    def pick(o, n, bdim=1):
        if o is None:
            return None
        shape = [1] * o.ndim
        shape[bdim] = o.shape[bdim]
        m = mask.reshape(shape)
        return jnp.where(m, n, o)

    return Cache(k=pick(old.k, new.k), v=pick(old.v, new.v),
                 conv_x=pick(old.conv_x, new.conv_x),
                 conv_bc=pick(old.conv_bc, new.conv_bc),
                 ssm=pick(old.ssm, new.ssm), pos=old.pos)
