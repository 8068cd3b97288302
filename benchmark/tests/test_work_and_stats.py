"""work.py against hand counts; the percentile and rate arithmetic."""

import pytest

from harness import stats, work
from harness.peaks import peaks_for


def test_flash_forward_hand_count():
    # one head, seq 4, head_dim 2: 10 (q, k) pairs under the causal mask;
    # QK^T: 10 dots of 2 MACs = 40 FLOPs; PV the same: 80 in all
    w = work.flash_attention_fwd(batch=1, heads=1, seq=4, head_dim=2)
    assert w["flops"] == 80
    assert w["bytes"] == 4 * (4 * 2) * 2          # q, k, v, o in bf16
    big = work.flash_attention_fwd(batch=8, heads=12, seq=1024, head_dim=64)
    assert big["flops"] == 8 * 12 * 4 * (1024 * 1025 / 2) * 64


def test_flash_backward_is_five_matmuls_and_eight_arrays():
    f = work.flash_attention_fwd(2, 3, 128, 64)
    b = work.flash_attention_bwd(2, 3, 128, 64)
    assert b["flops"] == pytest.approx(2.5 * f["flops"])
    assert b["bytes"] == 2 * f["bytes"]


def test_fused_update_hand_count():
    # params, grads, m, v + 1 EMA read; params, m, v + 1 EMA written: 9 x 4 B
    w = work.fused_adamw_ema(n_params=1000, n_ema=1)
    assert w["bytes"] == 1000 * 9 * 4
    r = work.roofline_seconds(w["flops"], w["bytes"], 197e12, 819e9)
    assert r["bound"] == "memory"
    assert r["seconds"] == pytest.approx(36000 / 819e9)


def test_param_count_matches_a_hand_count():
    from harness import reference_gpt2 as ref
    cfg = dict(n_embd=768, n_layer=12, n_head=12, vocab_size=50257,
               n_positions=1024)
    # 50257*768 + 1024*768 + 12 * (12*768**2 + 4*768) + 2*768, no linear bias
    assert ref.param_count(cfg) == 124356864


def test_kv_bytes():
    assert work.kv_bytes_per_token(36, 1280) == 184320
    assert work.decode_step_bytes(10.0, 2, 36, 1280) == 10.0 + 2 * 184320


def test_unknown_device_has_no_peaks():
    assert peaks_for("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("cpu")


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([], 95) is None


def test_a_stall_lowers_the_rate_and_raises_the_tail():
    """100 requests of 10 ms in a 1 s window, then the same with a 0.5 s
    stall that five requests sit through."""
    steady = [10.0] * 100
    stalled = [10.0] * 95 + [510.0] * 5 + [10.0] * 50
    assert stats.rate(100, 1.0) == 100.0
    assert stats.rate(150, 2.0) < stats.rate(100, 1.0)    # all the time
    assert stats.percentile(steady, 95) == 10.0
    assert stats.percentile(stalled, 95) == 10.0          # 5 of 150: 3.3 %
    assert stats.percentile(stalled + [510.0] * 4, 95) == 510.0
    assert stats.percentile(stalled, 99) == 510.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_iqr_share():
    assert stats.iqr_share([100, 100, 100, 100, 100, 100]) == 0
    assert stats.iqr_share([98, 99, 100, 100, 101, 102]) == \
        pytest.approx(0.025)
