"""Work counts and readers of the Keye-VL-2.0 cell: the sizes are the issue's
arithmetic, a reader without the program's grouped-pass counters reads
nothing, and with them the shares are what a hand computation gives."""

import json
import os
import types

import pytest

from harness import work_keye_vl2 as work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = json.load(open(os.path.join(
    HERE, "configs", "keye-vl-2.0-30b-a3b-pp8.json")))


def test_sizes_are_the_issue_s_arithmetic():
    s = work.sizes(CFG)
    assert s["expert"] == 3 * 2048 * 768 == 4_718_592      # 9,437,184 B
    assert s["head"] == 2048 * 151936
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512                 # 18.87 M
    index = 2048 * 1024 + 2048 * 64 + 2048 * 16             # 2.26 M
    router = 2048 * 128
    assert s["token"] == 6 * (attn + index + router)
    # a token passes 59.1 M matrix parameters a layer with its 8 experts
    assert round((s["token"] / 6 + 8 * s["expert"]) / 1e6, 1) == 59.1
    assert s["scored_flops"] == 2 * 16 * 64
    assert s["pair_flops"] == 2 * 32 * (128 + 128)
    assert 2 * s["kv_row"] == 2048 and s["index_key"] == 64


def _ctx():
    class Trace:
        window_s = 3.0

        def module_seconds(self, name):
            return {"jit_decode_fn": (1.6, 100),
                    "jit_prefill_chunk_fn": (1.2, 30)}.get(name, (0.0, 0))

        def op_seconds(self, name):
            return {"mla_block_attend": (0.2, 900),
                    "lightning_index_scores": (0.05, 900),
                    "grouped_expert_matmul": (0.9, 780)}.get(name,
                                                             (0.0, 0))
    return {"trace": Trace(), "config": CFG,
            "peaks": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9},
            "counters": {"traced": {
                "t": 3.0, "prompt_tokens": 30000, "prefill_steps": 30,
                "decode_steps": 100, "slot_steps_active": 1500}}}


COUNTED = {"prefill": {"expert_assignments_held": 30000 * 8 * 6,
                       "experts_touched": 30 * 6 * 128,
                       "index_rows_scored": 1.5e9, "kv_rows_attended": 3.4e8,
                       "kv_rows_live": 1.5e9,
                       "expert_rows_computed": 30 * 6 * 73000},
           "decode": {"expert_assignments_held": 1500 * 8 * 6,
                      "experts_touched": 100 * 6 * 79,
                      "index_rows_scored": 7.5e7, "kv_rows_attended": 1.8e7,
                      "kv_rows_live": 7.5e7,
                      "expert_rows_computed": 100 * 6 * 79 * 128}}


@pytest.fixture
def readers(monkeypatch):
    import run as bench_run
    reader, _ = bench_run.load_reader("step_mfu.serve.sparse_gqa")
    state = {"counted": None}
    monkeypatch.setattr(
        reader.__globals__["base"], "counted",
        lambda program: (state["counted"] or {}).get(program))
    return types.SimpleNamespace(**{
        k: v for k, v in reader.__globals__.items() if callable(v)}), state


def all_readers(mod):
    return (mod.step_mfu_serve_sparse_gqa, mod.prefill_mfu_serve_sparse_gqa,
            mod.decode_hbm_roofline_sparse_gqa,
            mod.attended_kv_share_sparse_gqa, mod.expert_rows_needed_share,
            mod.mla_block_attend_roofline_sparse_gqa,
            mod.lightning_index_scores_roofline_sparse_gqa,
            mod.grouped_expert_matmul_roofline)


def test_readers_read_nothing_without_the_grouped_counters(readers):
    """No counters at all, and the latent families' five (a program without
    the grouped pass, as the parent commit's): nothing is read, nothing
    raises."""
    mod, state = readers
    for f in all_readers(mod):
        assert f(_ctx()) is None
    state["counted"] = {
        program: {k: v for k, v in group.items()
                  if k != "expert_rows_computed"}
        for program, group in COUNTED.items()}
    for f in all_readers(mod):
        assert f(_ctx()) is None


def test_readers_against_a_hand_computation(readers):
    mod, state = readers
    state["counted"] = COUNTED
    ctx = _ctx()
    s = work.sizes(CFG)
    pre = 2 * (30000 * s["token"] + 30000 * 8 * 6 * s["expert"]) \
        + 1.5e9 * 2 * 16 * 64 + 3.4e8 * 2 * 32 * 256
    dec = 2 * (1500 * (s["token"] + s["head"])
               + 1500 * 8 * 6 * s["expert"]) \
        + 7.5e7 * 2 * 16 * 64 + 1.8e7 * 2 * 32 * 256
    assert mod.step_mfu_serve_sparse_gqa(ctx) == pytest.approx(
        100 * (pre + dec) / (3.0 * 197e12))
    assert mod.prefill_mfu_serve_sparse_gqa(ctx) == pytest.approx(
        100 * pre / (1.2 * 197e12))
    need = 2 * (100 * (s["token"] + s["head"]) + 100 * 6 * 79 * s["expert"]
                + 7.5e7 * 64 + 1.8e7 * 1024)      # the key, not its padding
    assert mod.decode_hbm_roofline_sparse_gqa(ctx) == pytest.approx(
        100 * need / 819e9 / 1.6)
    # a decode step of this reading: 5.9 GB, the experts three quarters
    assert 0.72 < 2 * 100 * 6 * 79 * s["expert"] / need < 0.78
    assert mod.attended_kv_share_sparse_gqa(ctx) == pytest.approx(
        100 * (3.4e8 + 1.8e7) / (1.5e9 + 7.5e7))
    assert mod.expert_rows_needed_share(ctx) == pytest.approx(
        100 * (30000 * 48 + 1500 * 48) / (30 * 6 * 73000 + 600 * 79 * 128))
    # both kernels are compute-bound: a key is read once a chunk of 1000
    assert mod.mla_block_attend_roofline_sparse_gqa(ctx) == pytest.approx(
        100 * (3.4e8 * 2 * 32 * 256 / 197e12) / 0.2)
    assert mod.lightning_index_scores_roofline_sparse_gqa(ctx) \
        == pytest.approx(100 * (1.5e9 * 2 * 16 * 64 / 197e12) / 0.05)
    # the grouped kernel is memory-bound at 64 rows an expert: the touched
    # experts' matrices and a row in and out an assignment
    assignments = 30000 * 48 + 1500 * 48
    touched = 30 * 6 * 128 + 100 * 6 * 79
    bytes_ = 2 * touched * s["expert"] + assignments * 2048 * 6
    assert bytes_ / 819e9 > 2 * assignments * s["expert"] / 197e12
    assert mod.grouped_expert_matmul_roofline(ctx) == pytest.approx(
        100 * (bytes_ / 819e9) / 0.9)
    for f in all_readers(mod):
        assert 0 < f(ctx) < 105
