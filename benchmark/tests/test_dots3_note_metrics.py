"""Work counts and readers of the dots3-note-prev cell: the sizes are the
issue's arithmetic by layer kind, a reader without the program's window
counters reads nothing, and with them the shares are what a hand computation
gives."""

import json
import os
import types

import pytest

from harness import work_dots3_note as work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = json.load(open(os.path.join(
    HERE, "configs", "dots3-note-prev-ep16.json")))


def test_sizes_are_the_issue_s_arithmetic():
    s = work.sizes(CFG)
    assert s["expert"] == 3 * 5120 * 1536 == 23_592_960
    assert s["head"] == 5120 * 19008
    full = 5120 * 1024 + 1024 * 128 * 192 + 5120 * 576 \
        + 512 * 128 * 256 + 128 * 128 * 5120 + 5120 * 128      # 134.7 M
    index = 1024 * 64 * 128 + 5120 * 128 + 5120 * 64           # 9.4 M
    sliding = 5120 * 1024 + 1024 * 64 * 256 + 5120 * 1088 \
        + 1024 * 64 * 320 + 64 * 128 * 5120 + 5120 * 64        # 90.8 M
    assert round((full + index) / 1e6, 2) == 144.05
    assert round(sliding / 1e6, 2) == 90.83
    assert s["full"]["attn"] == full and s["sliding"]["attn"] == sliding
    assert s["token"] == 3 * (full + index) + 6 * sliding \
        + 5120 * 13824 * 3 + 8 * (23_592_960 + 5120 * 256)
    assert s["full"]["pair_flops"] == 2 * 128 * 320
    assert s["sliding"]["pair_flops"] == 2 * 64 * 384
    assert (s["full"]["row"], s["sliding"]["row"]) == (576, 1088)


def _ctx():
    class Trace:
        window_s = 3.0

        def module_seconds(self, name):
            return {"jit_decode_fn": (0.5, 30),
                    "jit_prefill_chunk_fn": (2.0, 10)}.get(name, (0.0, 0))

        def op_seconds(self, name):
            return {"mla_block_attend": (0.4, 90),
                    "lightning_index_scores": (0.1, 30)}.get(name, (0.0, 0))
    return {"trace": Trace(), "config": CFG,
            "peaks": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9},
            "counters": {"traced": {
                "t": 3.0, "prompt_tokens": 20000, "prefill_steps": 10,
                "decode_steps": 30, "slot_steps_active": 400}}}


COUNTED = {"prefill": {"expert_assignments_held": 80000,
                       "experts_touched": 1200, "index_rows_scored": 3e8,
                       "kv_rows_attended": 1.1e8, "kv_rows_live": 3e8,
                       "window_rows_attended": 6e7, "window_rows_live": 6e8},
           "decode": {"expert_assignments_held": 1600,
                      "experts_touched": 1400, "index_rows_scored": 1e7,
                      "kv_rows_attended": 2.4e6, "kv_rows_live": 1e7,
                      "window_rows_attended": 1.2e6,
                      "window_rows_live": 2e7}}


@pytest.fixture
def readers(monkeypatch):
    import run as bench_run
    reader, _ = bench_run.load_reader("step_mfu.serve.mixed")
    state = {"counted": None}
    monkeypatch.setattr(
        reader.__globals__["base"], "counted",
        lambda program: (state["counted"] or {}).get(program))
    return types.SimpleNamespace(**{
        k: v for k, v in reader.__globals__.items() if callable(v)}), state


def all_readers(mod):
    return (mod.step_mfu_serve_mixed, mod.prefill_mfu_serve_mixed,
            mod.decode_hbm_roofline_mixed_latent,
            mod.attended_kv_share_mixed,
            mod.mla_block_attend_roofline_mixed,
            mod.lightning_index_scores_roofline_mixed)


def test_readers_read_nothing_without_the_window_counters(readers):
    """No counters at all, and the DeepSeek family's five (a program
    without window layers): nothing is read, nothing raises."""
    mod, state = readers
    for f in all_readers(mod):
        assert f(_ctx()) is None
    state["counted"] = {
        program: {k: v for k, v in group.items() if "window" not in k}
        for program, group in COUNTED.items()}
    for f in all_readers(mod):
        assert f(_ctx()) is None


def test_readers_against_a_hand_computation(readers):
    mod, state = readers
    state["counted"] = COUNTED
    ctx = _ctx()
    s = work.sizes(CFG)
    pre = 2 * (20000 * s["token"] + 80000 * s["expert"]) \
        + 3e8 * 2 * 64 * 128 + 1.1e8 * 2 * 128 * 320 + 6e7 * 2 * 64 * 384
    dec = 2 * (400 * (s["token"] + s["head"]) + 1600 * s["expert"]) \
        + 1e7 * 2 * 64 * 128 + 2.4e6 * 2 * 128 * 320 + 1.2e6 * 2 * 64 * 384
    assert mod.step_mfu_serve_mixed(ctx) == pytest.approx(
        100 * (pre + dec) / (3.0 * 197e12))
    assert mod.prefill_mfu_serve_mixed(ctx) == pytest.approx(
        100 * pre / (2.0 * 197e12))
    need = 2 * (30 * (s["token"] + s["head"]) + 1400 * s["expert"]
                + 1e7 * 128 + 2.4e6 * 576 + 1.2e6 * 1088)
    assert mod.decode_hbm_roofline_mixed_latent(ctx) == pytest.approx(
        100 * need / 819e9 / 0.5)
    assert mod.attended_kv_share_mixed(ctx) == pytest.approx(
        100 * (1.1e8 + 2.4e6 + 6e7 + 1.2e6) / (3e8 + 1e7 + 6e8 + 2e7))
    # both kernels are compute-bound: a key is read once a chunk of 2000
    assert mod.mla_block_attend_roofline_mixed(ctx) == pytest.approx(
        100 * ((1.1e8 * 2 * 128 * 320 + 6e7 * 2 * 64 * 384) / 197e12) / 0.4)
    assert mod.lightning_index_scores_roofline_mixed(ctx) == pytest.approx(
        100 * (3e8 * 2 * 64 * 128 / 197e12) / 0.1)
    for f in all_readers(mod):
        assert 0 < f(ctx) < 105
