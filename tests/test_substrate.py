"""Substrate tests: optimizer, train step, data, checkpoint fault
tolerance, gradient compression, watchdog, serve engine."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get_arch
from repro.configs import reduce_for_smoke
from repro.data import SyntheticLM
from repro.data import make_batch
from repro.models import decode_step
from repro.models import init_params
from repro.models import prefill
from repro.serve import Request
from repro.serve import ServeEngine
from repro.train import AdamWConfig
from repro.train import StepWatchdog
from repro.train import compressed_psum_mean
from repro.train import init_error_feedback
from repro.train import init_train_state
from repro.train import lr_schedule
from repro.train import make_train_step
from repro.train import opt_logical_axes
from repro.train import param_logical_axes

CFG = reduce_for_smoke(get_arch("llama3.2-3b"))


# ---------------------------------------------------------------------------
# optimizer / train loop
# ---------------------------------------------------------------------------
def test_lr_schedule_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                      min_lr_ratio=0.1)
    lrs = [float(lr_schedule(cfg, jnp.asarray(s))) for s in
           (0, 5, 10, 55, 100)]
    assert lrs[0] == 0.0
    assert lrs[1] == pytest.approx(0.5)
    assert lrs[2] == pytest.approx(1.0)
    assert 0.1 < lrs[3] < 1.0
    assert lrs[4] == pytest.approx(0.1, rel=1e-3)


@pytest.mark.slow
def test_train_loss_decreases():
    params = init_params(CFG, jax.random.key(0))
    state = init_train_state(params)
    step = jax.jit(make_train_step(
        CFG, AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=40)))
    data = SyntheticLM(CFG.vocab, seq_len=64, global_batch=8)
    losses = []
    for i in range(20):
        state, metrics = step(state, jnp.asarray(data.batch(i)))
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.2


@pytest.mark.slow
def test_grad_accumulation_matches_full_batch():
    params = init_params(CFG, jax.random.key(0))
    tokens = jnp.asarray(make_batch(CFG.vocab, 8, 32))
    opt = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=10)
    s1, m1 = jax.jit(make_train_step(CFG, opt, microbatches=1))(
        init_train_state(params), tokens)
    s2, m2 = jax.jit(make_train_step(CFG, opt, microbatches=4))(
        init_train_state(params), tokens)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-3)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(a.astype(np.float32),
                                   b.astype(np.float32), rtol=2e-2,
                                   atol=2e-3)


def test_param_axes_structure_matches_params():
    for name in ("llama3.2-3b", "deepseek-moe-16b", "mamba2-2.7b",
                 "zamba2-7b"):
        cfg = reduce_for_smoke(get_arch(name))
        params = init_params(cfg, jax.random.key(0))
        axes = param_logical_axes(cfg)
        pl = jax.tree_util.tree_structure(params)
        al = jax.tree_util.tree_structure(
            axes, is_leaf=lambda x: isinstance(x, tuple))
        assert pl == al, f"{name}: axes tree != params tree"
        # every axes tuple has the same rank as its param
        flat_p = jax.tree.leaves(params)
        flat_a = jax.tree.leaves(axes,
                                 is_leaf=lambda x: isinstance(x, tuple))
        for p, a in zip(flat_p, flat_a):
            assert p.ndim == len(a), f"{name}: rank mismatch {p.shape} {a}"
        # ZeRO axes add 'zero' only on unsharded leading dims
        zaxes = jax.tree.leaves(opt_logical_axes(cfg),
                                is_leaf=lambda x: isinstance(x, tuple))
        for a, z in zip(flat_a, zaxes):
            assert len(a) == len(z)


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------
def test_compressed_psum_single_shard_roundtrip():
    """On a 1-device axis the compressed mean must equal g up to int8
    quantization error, and error feedback must capture the residual."""
    mesh = jax.make_mesh((1,), ("pod",))
    g = {"w": jnp.linspace(-1.0, 1.0, 64).reshape(8, 8)}
    e = init_error_feedback(g)

    def f(g, e):
        return compressed_psum_mean(g, e, "pod")

    from jax.sharding import PartitionSpec as P
    out, err = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        check_vma=False))(g, e)
    q_err = np.abs(np.asarray(out["w"]) - np.asarray(g["w"]))
    assert q_err.max() <= (1.0 / 127.0) + 1e-6
    np.testing.assert_allclose(np.asarray(err["w"]),
                               np.asarray(g["w"] - out["w"]), atol=1e-6)


def test_compressed_psum_error_feedback_converges():
    """Repeatedly syncing the same gradient with error feedback must
    average out the quantization bias (sum of dequantized ≈ sum of true)."""
    mesh = jax.make_mesh((1,), ("pod",))
    g = {"w": jnp.asarray([[0.003, -0.7], [0.31, 0.02]])}
    e = init_error_feedback(g)
    from jax.sharding import PartitionSpec as P
    f = jax.jit(jax.shard_map(
        lambda g, e: compressed_psum_mean(g, e, "pod"), mesh=mesh,
        in_specs=(P(), P()), out_specs=(P(), P()), check_vma=False))
    total = jnp.zeros_like(g["w"])
    for _ in range(50):
        out, e = f(g, e)
        total = total + out["w"]
    np.testing.assert_allclose(np.asarray(total) / 50,
                               np.asarray(g["w"]), atol=2e-3)


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------
def test_data_deterministic_and_sharded():
    d1 = SyntheticLM(1000, 128, 16, seed=7, n_shards=4, shard=2)
    d2 = SyntheticLM(1000, 128, 16, seed=7, n_shards=4, shard=2)
    np.testing.assert_array_equal(d1.batch(5), d2.batch(5))
    assert d1.batch(5).shape == (4, 128)
    d3 = SyntheticLM(1000, 128, 16, seed=7, n_shards=4, shard=3)
    assert not np.array_equal(d1.batch(5), d3.batch(5))
    assert (d1.batch(0) < 1000).all() and (d1.batch(0) >= 0).all()


# ---------------------------------------------------------------------------
# checkpointing / fault tolerance
# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip_and_pruning(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_n=2)
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.bfloat16),
            "b": {"c": jnp.ones((4,), jnp.float32)}}
    for step in (1, 2, 3):
        mgr.save(step, jax.tree.map(lambda x: x * step, tree))
    assert mgr._steps() == [2, 3]            # pruned to keep_n
    step, restored = mgr.restore_latest(tree)
    assert step == 3
    np.testing.assert_allclose(np.asarray(restored["b"]["c"]),
                               3 * np.ones(4))


def test_checkpoint_survives_corruption(tmp_path):
    """Corrupting the newest checkpoint must fall back to the previous
    valid one (node-failure torn-write scenario)."""
    mgr = CheckpointManager(str(tmp_path), keep_n=3)
    tree = {"w": jnp.ones((8,), jnp.float32)}
    mgr.save(1, tree)
    mgr.save(2, jax.tree.map(lambda x: x * 2, tree))
    # corrupt step 2's arrays
    npz = os.path.join(str(tmp_path), "step_00000002", "arrays.npz")
    with open(npz, "r+b") as f:
        f.seek(30)
        f.write(b"\x00" * 64)
    step, restored = mgr.restore_latest(tree)
    assert step == 1
    np.testing.assert_allclose(np.asarray(restored["w"]), np.ones(8))


@pytest.mark.slow
def test_checkpoint_resume_training(tmp_path):
    """Kill-and-resume: state restored from disk continues bit-exactly."""
    params = init_params(CFG, jax.random.key(0))
    state = init_train_state(params)
    opt = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=10)
    step_fn = jax.jit(make_train_step(CFG, opt))
    data = SyntheticLM(CFG.vocab, 32, 4)
    mgr = CheckpointManager(str(tmp_path))
    for i in range(3):
        state, _ = step_fn(state, jnp.asarray(data.batch(i)))
    mgr.save(3, state)
    state_a = state
    for i in range(3, 5):
        state_a, _ = step_fn(state_a, jnp.asarray(data.batch(i)))
    # simulated preemption: fresh process restores and replays
    step0, state_b = mgr.restore_latest(init_train_state(params))
    assert step0 == 3
    for i in range(3, 5):
        state_b, _ = step_fn(state_b, jnp.asarray(data.batch(i)))
    for a, b in zip(jax.tree.leaves(state_a.params),
                    jax.tree.leaves(state_b.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------
def test_watchdog_flags_stragglers():
    evicted = []
    wd = StepWatchdog(threshold=3.0, evict_after=2,
                      on_straggler=lambda s, d: evicted.append(s))
    for s in range(10):
        assert not wd.record(s, 1.0)
    assert wd.record(10, 10.0)
    assert wd.record(11, 12.0)
    assert evicted == [11]
    assert not wd.record(12, 1.0)      # recovery resets the streak


# ---------------------------------------------------------------------------
# serve engine
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_serve_engine_batched_matches_single():
    cfg = CFG
    params = init_params(cfg, jax.random.key(0))

    def reference_decode(prompt, n):
        logits, cache = jax.jit(lambda p, t: prefill(p, t, cfg))(
            params, jnp.asarray(prompt[None]))
        # pad cache seq to engine max_seq
        pad = 64 - cache.k.shape[2]
        cache = cache._replace(
            k=jnp.pad(cache.k, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
            v=jnp.pad(cache.v, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))))
        out = [int(jnp.argmax(logits[0]))]
        dec = jax.jit(lambda p, t, c: decode_step(p, t, c, cfg))
        for _ in range(n - 1):
            lg, cache = dec(params, jnp.asarray([[out[-1]]]), cache)
            out.append(int(jnp.argmax(lg[0, 0])))
        return out

    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, cfg.vocab, size=L).astype(np.int32)
               for L in (7, 13, 10)]
    engine = ServeEngine(cfg, params, max_batch=2, max_seq=64)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=5)
            for i, p in enumerate(prompts)]
    for r in reqs:
        engine.add_request(r)
    engine.run_to_completion()
    for r, p in zip(reqs, prompts):
        assert r.done and len(r.tokens_out) == 5
        assert r.tokens_out == reference_decode(p, 5), \
            f"request {r.uid} diverged"
