"""Work counts and readers of the DeepSeek-V3.2-Exp cell: the sizes are the
issue's arithmetic, a reader without the program's counters reads nothing,
and with them the shares are what a hand computation gives."""

import json
import os

import pytest

from harness import work_deepseek_v32 as work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = json.load(open(os.path.join(
    HERE, "configs", "deepseek-v3.2-exp-ep16.json")))


def test_sizes_are_the_issue_s_arithmetic():
    s = work.sizes(CFG)
    assert s["expert"] == 3 * 7168 * 2048 == 44_040_192
    assert s["head"] == 7168 * 16160
    attn = 7168 * 1536 + 1536 * 128 * 192 + 7168 * 576 \
        + 512 * 128 * 256 + 128 * 128 * 7168               # 187.1 M
    index = 1536 * 64 * 128 + 7168 * 128 + 7168 * 64       # 14.0 M
    assert round(attn / 1e6, 1) == 187.1 and round(index / 1e6, 1) == 14.0
    assert s["token"] == 6 * (attn + index) + 3 * 7168 * 18432 \
        + 5 * (44_040_192 + 7168 * 256)


def _ctx(ring_args):
    class Trace:
        window_s = 3.0

        def module_seconds(self, name):
            return {"jit_decode_fn": (0.5, 30),
                    "jit_prefill_chunk_fn": (2.0, 10)}.get(name, (0.0, 0))

        def op_seconds(self, name):
            return {"mla_block_attend": (0.4, 60),
                    "lightning_index_scores": (0.1, 60)}.get(name, (0.0, 0))
    return {"trace": Trace(), "config": CFG,
            "peaks": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9},
            "counters": {"traced": {
                "t": 3.0, "prompt_tokens": 20000, "prefill_steps": 10,
                "decode_steps": 30, "slot_steps_active": 400}}}, ring_args


COUNTED = {"prefill": {"expert_assignments_held": 50000,
                       "experts_touched": 800, "index_rows_scored": 5e8,
                       "kv_rows_attended": 2.4e8, "kv_rows_live": 5e8},
           "decode": {"expert_assignments_held": 1000,
                      "experts_touched": 900, "index_rows_scored": 2e7,
                      "kv_rows_attended": 5e6, "kv_rows_live": 2e7}}


@pytest.fixture
def readers(monkeypatch):
    import run as bench_run
    import types
    reader, _ = bench_run.load_reader("step_mfu.serve.routed")
    state = {"counted": None}
    # the reader's module is loaded by path: reach it through a function
    monkeypatch.setitem(
        reader.__globals__, "counted",
        lambda program: (state["counted"] or {}).get(program))
    return types.SimpleNamespace(**{
        k: v for k, v in reader.__globals__.items() if callable(v)}), state


def test_readers_read_nothing_without_the_counters(readers):
    mod, _ = readers
    ctx, _ = _ctx(None)
    for f in (mod.step_mfu_serve_routed, mod.prefill_mfu_serve,
              mod.decode_hbm_roofline_sparse_latent, mod.attended_kv_share,
              mod.mla_block_attend_roofline,
              mod.lightning_index_scores_roofline):
        assert f(ctx) is None


def test_readers_against_a_hand_computation(readers):
    mod, state = readers
    state["counted"] = COUNTED
    ctx, _ = _ctx(None)
    s = work.sizes(CFG)
    pre = 2 * (20000 * s["token"] + 50000 * s["expert"]) \
        + 5e8 * 2 * 64 * 128 + 2.4e8 * 2 * 128 * 320
    dec = 2 * (400 * (s["token"] + s["head"]) + 1000 * s["expert"]) \
        + 2e7 * 2 * 64 * 128 + 5e6 * 2 * 128 * 320
    assert mod.step_mfu_serve_routed(ctx) == pytest.approx(
        100 * (pre + dec) / (3.0 * 197e12))
    assert mod.prefill_mfu_serve(ctx) == pytest.approx(
        100 * pre / (2.0 * 197e12))
    need = 2 * (30 * (s["token"] + s["head"]) + 900 * s["expert"]
                + 2e7 * 128 + 5e6 * 576)
    assert mod.decode_hbm_roofline_sparse_latent(ctx) == pytest.approx(
        100 * need / 819e9 / 0.5)
    assert mod.attended_kv_share(ctx) == pytest.approx(
        100 * (2.4e8 + 5e6) / (5e8 + 2e7))
    # both kernels are compute-bound: a key is read once a chunk of 2000
    assert mod.mla_block_attend_roofline(ctx) == pytest.approx(
        100 * (2.4e8 * 2 * 128 * 320 / 197e12) / 0.4)
    assert mod.lightning_index_scores_roofline(ctx) == pytest.approx(
        100 * (5e8 * 2 * 64 * 128 / 197e12) / 0.1)
    for f in (mod.step_mfu_serve_routed, mod.prefill_mfu_serve,
              mod.mla_block_attend_roofline,
              mod.lightning_index_scores_roofline):
        assert 0 < f(ctx) < 105

