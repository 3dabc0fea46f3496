"""Smoke test on one TPU: the Pallas kernels and full-width serving.

    python3 chip_smoke.py

Refuses to run unless JAX's first device is a TPU.  Two phases, each of
which raises on failure:

1. kernels — ``flash_attention``, ``decode_attention`` and ``ssd_scan``
   compiled for the chip (``interpret=False``) at Llama-3.2-3B /
   Mamba2-2.7B widths, each compared with its float32 ``ref.py`` oracle;
2. serve — Llama-3.2-3B at its published widths (random weights from a
   seed) served through ``ServeEngine``: every request gets exactly its
   token budget, and one decode step's logits for a slot match ``forward``
   on the same tokens.

The timings printed are smoke numbers, not benchmark metrics.  The last
line of standard output is the JSON verdict
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import json
from pathlib import Path
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.configs import ArchConfig
from repro.configs import get_arch
from repro.kernels import attention_ref
from repro.kernels import decode_attention
from repro.kernels import decode_attention_ref
from repro.kernels import flash_attention
from repro.kernels import ssd_ref
from repro.kernels import ssd_scan
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import ServeStats
from repro.launch.serve import init_params_on_device
from repro.launch.serve import make_requests
from repro.launch.serve import serve
from repro.models import forward
from repro.serve import ServeEngine
from repro.serve.engine import _splice

# Both attention kernels and their oracles return bf16, whose ulp is 2^-7
# of the binade (1.6e-2 for |o| in [2, 4)), and the kernels also round the
# softmax weights to bf16 before the PV matmul.  Same bound as the
# interpret-mode tests (tests/test_kernels.py).
ATTN_TOL = 2e-2
# ssd_scan contracts in f32 at fp32 precision; its y is rounded to bf16
# (up to 2^-8 relative, 6.2e-2 at |y| ~ 30) and its state stays f32.
# Same bound as the interpret-mode tests.
SSD_TOL = 3e-2
# Decode and forward are the same bf16 model in two programs that round at
# different points (other matmul shapes and accumulation orders).  Each
# layer adds a few independent bf16 roundings of ~2^-9 r.m.s.; over 28
# layers (~100 roundings) they grow to ~10 x 2^-9 = 2% of the logits' norm.
LOGITS_REL_TOL = 3e-2


def _check_close(name: str, out, ref, tol: float) -> None:
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    err = np.abs(out - ref)
    print(f"smoke: kernel {name}: max |err| {err.max():.3e} "
          f"(bound {tol} + {tol}*|ref|)")
    if not np.all(err <= tol + tol * np.abs(ref)):
        raise AssertionError(f"{name} differs from its oracle beyond {tol}")


def _oracle(fn, *args, **static):
    """``fn`` in float32 at full matmul precision (the TPU's default rounds
    f32 matmul operands to bf16)."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(fn, static_argnames=tuple(static))(*args, **static)


def kernel_phase(seed: int = 0) -> None:
    """Run each Pallas kernel once on the chip against its oracle."""
    ks = jax.random.split(jax.random.key(seed), 12)

    def normal(k, shape, dtype=jnp.bfloat16):
        return jax.random.normal(k, shape).astype(dtype)

    # flash: Llama-3.2-3B prefill widths, 1024 pinned + 3072 streamed rows
    q = normal(ks[0], (1, 4096, 24, 128))
    k = normal(ks[1], (1, 4096, 8, 128))
    v = normal(ks[2], (1, 4096, 8, 128))
    _check_close("flash_attention",
                 flash_attention(q, k, v, causal=True, pinned_rows=1024),
                 _oracle(attention_ref, q, k, v), ATTN_TOL)

    # decode: 8 sequences of ragged length over an 8192-row cache
    q = normal(ks[3], (8, 24, 128))
    k = normal(ks[4], (8, 8192, 8, 128))
    v = normal(ks[5], (8, 8192, 8, 128))
    lens = jax.random.randint(ks[6], (8,), 1, 8193)
    _check_close("decode_attention", decode_attention(q, k, v, lens),
                 _oracle(decode_attention_ref, q, k, v, lens), ATTN_TOL)

    # ssd: Mamba2-2.7B head widths (H=80, P=64, N=128, chunk 256)
    x = normal(ks[7], (1, 2048, 80, 64))
    dt = jax.nn.softplus(normal(ks[8], (1, 2048, 80), jnp.float32)) * 0.1
    A = -jnp.exp(jax.random.uniform(ks[9], (80,), minval=-1.0, maxval=1.0))
    B = normal(ks[10], (1, 2048, 1, 128))
    C = normal(ks[11], (1, 2048, 1, 128))
    y, state = ssd_scan(x, dt, A, B, C, chunk=256)
    y_ref, state_ref = _oracle(ssd_ref, x, dt, A, B, C, chunk=256)
    _check_close("ssd_scan y", y, y_ref, SSD_TOL)
    _check_close("ssd_scan state", state, state_ref, SSD_TOL)


def check_decode_logits(engine: ServeEngine, prompt: np.ndarray,
                        slot: int) -> float:
    """Prefill ``prompt`` into ``slot`` of the engine's pooled cache, decode
    one token with the engine's own compiled programs, and compare that
    slot's logits with ``forward`` over prompt + token.  Returns the
    relative error ||decode - forward|| / ||forward||."""
    plen = len(prompt)
    logits, one = engine._prefill(engine.params, jnp.asarray(prompt[None]))
    tok = int(jnp.argmax(logits[0]))
    cache = _splice(engine.cache, one, slot)
    toks = np.zeros((engine.max_batch, 1), np.int32)
    toks[slot, 0] = tok
    dec, _ = engine._decode(engine.params, jnp.asarray(toks),
                            cache._replace(pos=jnp.asarray(plen, jnp.int32)))
    seq = jnp.asarray(np.append(prompt, tok)[None])
    ref = jax.jit(forward, static_argnums=2, static_argnames="remat")(
        engine.params, seq, engine.cfg, remat=False)
    got = np.asarray(dec[slot, 0], np.float32)
    want = np.asarray(ref[0, -1], np.float32)
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    print(f"smoke: decode-vs-forward logits: relative error {rel:.3e} "
          f"(bound {LOGITS_REL_TOL}), max |err| "
          f"{np.abs(got - want).max():.3e} of max |logit| "
          f"{np.abs(want).max():.3e}")
    if not rel <= LOGITS_REL_TOL:
        raise AssertionError(f"decode logits differ from forward: {rel}")
    return rel


def serve_phase(cfg: ArchConfig, *, seed: int = 0, max_batch: int = 4,
                max_seq: int = 1024, n_requests: int = 8,
                prompt_lens=(64, 128, 256), max_new: int = 16
                ) -> ServeStats:
    """Serve ``n_requests`` seeded requests through ``ServeEngine`` and
    check what comes out; raises on any failed check."""
    t0 = time.perf_counter()
    params = init_params_on_device(cfg, seed)
    jax.block_until_ready(params)
    print(f"smoke: init_params (compile + run) "
          f"{time.perf_counter() - t0:.2f}s")
    engine = ServeEngine(cfg, params, max_batch=max_batch, max_seq=max_seq)
    lens = np.random.default_rng(seed).choice(prompt_lens, size=n_requests)
    reqs = make_requests(cfg.vocab, lens, max_new=max_new, seed=seed)
    stats = serve(engine, reqs)
    for r in reqs:
        if not r.done or len(r.tokens_out) != max_new:
            raise AssertionError(f"request {r.uid}: {len(r.tokens_out)} "
                                 f"tokens, expected {max_new}")
        if not all(0 <= t < cfg.vocab for t in r.tokens_out):
            raise AssertionError(f"request {r.uid}: token out of vocab")
    check_decode_logits(engine, reqs[0].prompt, slot=max_batch - 1)
    return stats


def main() -> int:
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is on "
              f"platform {dev.platform!r}", file=sys.stderr)
        return 1
    print(f"smoke: device {dev.device_kind} x{len(devices)}, compile cache "
          f"{enable_compile_cache()}")

    t0 = time.perf_counter()
    kernel_phase()
    print(f"smoke: kernel phase {time.perf_counter() - t0:.2f}s")

    cfg = get_arch("llama3.2-3b")
    stats = serve_phase(cfg)
    print(f"smoke: serve {cfg.name} compile+warm-up {stats.warmup_s:.2f}s; "
          f"timed run {stats.run_s:.3f}s, {stats.steps} engine steps "
          f"({stats.steps / stats.run_s:.2f} steps/s), {stats.tokens} "
          f"tokens ({stats.tokens / stats.run_s:.2f} tok/s)")
    mem = dev.memory_stats() or {}
    print(f"smoke: peak_bytes_in_use (whole process) "
          f"{mem.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
