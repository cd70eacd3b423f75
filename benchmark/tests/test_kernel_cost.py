"""Operations and bytes of the Pallas kernels against hand arithmetic and
against ``lib/flops.py``'s attention term."""

import pytest

from benchmark.lib import kernel_cost as K
from benchmark.lib.flops import kv_bytes_per_token, train_flops_per_token
from benchmark.lib.peaks import device_peaks

V5E = device_peaks("TPU v5 lite")
GPT2 = {"n_layer": 24, "n_head": 16, "d_model": 1024, "vocab_size": 50304}


def test_attended_pairs():
    assert K.attended_pairs(4, 4, causal=False) == 16
    assert K.attended_pairs(4, 4, causal=True) == 10          # 1 + 2 + 3 + 4
    # queries are the last t of s positions: each sees s - t more keys
    assert K.attended_pairs(2, 5, causal=True) == 4 + 5


def test_flash_fwd_at_the_gpt2_cells_shapes():
    """B 16 x H 16, T 1024, head size 64, bf16 (the ISSUE's arithmetic)."""
    c = K.flash_fwd(256, 1024, 1024, 64)
    assert c.flops == pytest.approx(2 * 256 * 1024 * 1025 * 64)   # 34.4 GFLOP
    assert c.flops == pytest.approx(34.4e9, rel=0.01)
    qkvo = 4 * 256 * 1024 * 64 * 2
    assert c.bytes == qkvo + 256 * 1024 * 4
    padded = K.flash_fwd(256, 1024, 1024, 64, lse_lanes=128)
    assert padded.bytes == qkvo + 256 * 1024 * 128 * 4            # 268 MB
    assert padded.bytes == pytest.approx(268e6, rel=0.01)
    assert c.bound(V5E) == "flops" and padded.bound(V5E) == "bytes"
    assert c.floor_s(V5E) == pytest.approx(0.1746e-3, rel=0.01)
    assert padded.floor_s(V5E) == pytest.approx(0.328e-3, rel=0.01)


def test_flash_costs_add_up_to_the_models_attention_term():
    """Full square, forward x 3 (backward counted as twice the forward, the
    convention of ``train_flops_per_token``) over the tokens is 12 L d T."""
    b, t, h, d = 2, 512, GPT2["n_head"], GPT2["d_model"]
    fwd = K.flash_fwd(b * h, t, t, d // h, causal=False)
    per_token = 3 * GPT2["n_layer"] * fwd.flops / (b * t)
    model = dict(GPT2, d_ff=4 * d)
    attention = (train_flops_per_token(model, t)
                 - train_flops_per_token(model, 0))
    assert per_token == pytest.approx(attention)
    assert attention == pytest.approx(12 * 24 * d * t)


def test_backward_kernels_count_their_own_matrix_products():
    fwd = K.flash_fwd(8, 256, 256, 64)
    dq = K.flash_bwd_dq(8, 256, 256, 64)
    dkv = K.flash_bwd_dkv(8, 256, 256, 64)
    delta = K.flash_bwd_delta(8, 256, 64)
    assert dq.flops == pytest.approx(1.5 * fwd.flops)     # 3 products for 2
    assert dkv.flops == pytest.approx(2.0 * fwd.flops)    # 4 products for 2
    assert delta.flops == 2 * 8 * 256 * 64
    assert delta.bytes == 2 * 8 * 256 * 64 * 2 + 8 * 256 * 4
    # dq: q, k, v, dO in bf16, dq out in float32, one lse and one delta row
    assert dq.bytes == 4 * 8 * 256 * 64 * 2 + 8 * 256 * 64 * 4 + 2 * 8 * 256 * 4
    assert dkv.bytes == dq.bytes + 8 * 256 * 64 * 4
    both = dq + dkv
    assert both.flops == dq.flops + dkv.flops
    assert both.bytes == dq.bytes + dkv.bytes


def test_paged_decode_reads_every_live_key_and_value_once():
    model = {"n_layer": 24, "n_head": 16, "d_model": 2048}
    c = K.paged_decode(1000, 16, 128)
    assert c.bytes * model["n_layer"] == 1000 * kv_bytes_per_token(model)
    assert c.flops == 4 * 1000 * 2048
    assert c.bound(V5E) == "bytes"
    assert c.floor_s(V5E) == pytest.approx(c.bytes / 819e9)
