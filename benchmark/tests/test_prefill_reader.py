"""readers/prefill.py on a made-up context: the prefill program's device
seconds and the scheduler's count of real prompt tokens in, the share of the
bf16 peak out; no such program in the trace (the chunked family), no trace,
or no prompt token prefilled in the traced window reads nothing."""

import pytest

import run as bench_run

N_PARAMS = 773_615_360            # GPT-2-large without linear biases


def ctx(prefill_seconds, dispatches, prompt_tokens=6_556, trace=True,
        program="jit_prefill_fn"):
    class Trace:
        def module_seconds(self, name):
            return {program: (prefill_seconds, dispatches)}.get(
                name, (0.0, 0))
    return {"trace": Trace() if trace else None,
            "peaks": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9},
            "counters": {"n_params": N_PARAMS, "traced": {
                "t": 3.0, "prefill_steps": dispatches,
                "prompt_tokens": prompt_tokens,
                "prefill_token_slots": dispatches * 4096}}}


def test_share_is_real_prompt_flops_over_program_time_over_peak():
    reader, spec = bench_run.load_reader("prefill_mfu.serve.dense")
    assert spec["unit"] == "%" and spec["moves"] == "serve_tok_s"
    assert spec["layer"] == "model step" and spec["source"] == "device_trace"
    # the parent's traced 3 s (PERF.md, PR 30): 25 dispatches of [8, 512]
    # in 1.66 s carried 6,556 prompt tokens of 102,400 positions
    got = reader(ctx(1.66, 25))
    assert got == pytest.approx(
        100 * 2 * N_PARAMS * 6_556 / (1.66 * 197e12))
    assert got == pytest.approx(3.1, abs=0.05)
    # the same tokens in a seventh of the program time read seven times it
    assert reader(ctx(1.66 / 7, 25)) == pytest.approx(7 * got)


@pytest.mark.parametrize("context", [
    ctx(0.0, 0),                                   # no such program ran
    ctx(1.66, 25, program="jit_prefill_chunk_fn"),  # the chunked family
    ctx(1.66, 25, trace=False),                    # an untraced run
    ctx(1.66, 25, prompt_tokens=0),                # no prompt token traced
], ids=["no_program", "other_program", "no_trace", "no_prompt_tokens"])
def test_reads_nothing_where_there_is_nothing_to_read(context):
    reader, _spec = bench_run.load_reader("prefill_mfu.serve.dense")
    assert reader(context) is None


def test_benchmark_json_lists_the_metric_for_the_dense_serve_cell():
    bench = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "prefill_mfu.serve.dense"]
    assert entry == {
        "name": "prefill_mfu.serve.dense", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "model step",
        "moves": "serve_tok_s", "workloads": ["gpt2-large.serve.closed16"]}
    assert bench["per_layer"][-1] is entry        # appended, not inserted
