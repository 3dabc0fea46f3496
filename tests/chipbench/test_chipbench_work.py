"""Required-work counts against hand counts at tiny configurations."""

from __future__ import annotations

from chipbench.work import dense
from chipbench.work import ssm
import pytest

DENSE = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
         "vocab_size": 256}
SSM = {"n_layer": 2, "d_model": 64, "vocab_size": 250,
       "pad_vocab_size_multiple": 16,
       "ssm_cfg": {"d_state": 16, "d_conv": 4, "expand": 2, "headdim": 16,
                   "ngroups": 1}}


def test_dense_layer_params():
    # q and o: 64*4*16 each; k and v: 64*2*16 each; SwiGLU: 3*64*128
    assert dense.layer_matmul_params(DENSE) == 4096 * 2 + 2048 * 2 + 24576


def test_dense_decode_counts():
    flops, data = dense.decode(DENSE, [5, 7])
    w = 2 * 36864 + 64 * 256               # layers + head
    attn = 4 * 5 * 4 * 16 * 2 + 4 * 7 * 4 * 16 * 2
    assert flops == pytest.approx(2 * 2 * w + attn)
    kv_token = 2 * 2 * 2 * 16 * 2          # layers * (k, v) * heads * dim * 2 B
    assert data == pytest.approx(w * 2 + 2 * 64 * 2 + 12 * kv_token
                                 + 2 * kv_token)


def test_dense_prefill_counts_causal_attention_once():
    flops, data = dense.prefill(DENSE, 8)
    assert flops == pytest.approx(2 * 2 * 36864 * 8 + 2 * 64 * 256
                                  + 2 * 4 * 4 * 16 * 8 * 9 / 2)
    assert data == pytest.approx((2 * 36864 + 64 * 256) * 2 + 8 * 64 * 2
                                 + 8 * 256)


def test_ssm_decode_counts():
    # in-proj 64*(2*128 + 32 + 8), out-proj 128*64 per layer
    assert ssm.layer_matmul_params(SSM) == 64 * 296 + 128 * 64
    flops, data = ssm.decode(SSM, [10, 20, 30])
    w = 2 * 27136 + 64 * 256               # padded vocab 256
    state = 2 * 8 * 16 * 16                # layers * heads * headdim * d_state
    conv = 2 * 2 * 4 * (128 + 32)
    assert flops == pytest.approx(3 * (2 * w + 5 * state + conv))
    conv_state = 2 * 3 * 160 * 2           # layers * (k-1) * channels * 2 B
    assert data == pytest.approx(w * 2 + 3 * 64 * 2
                                 + 3 * 2 * (state * 4 + conv_state))


def test_ssm_prefill_grows_linearly_in_length():
    f1, _ = ssm.prefill(SSM, 64)
    f2, _ = ssm.prefill(SSM, 128)
    head = 2 * 64 * 256
    assert f2 - head == pytest.approx(2 * (f1 - head))
