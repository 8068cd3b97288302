"""readers/flash_decode.py on a made-up context: kernel seconds and the
scheduler's live-token count in, the share of the HBM roofline out; no such
kernel in the trace (the parent's program, the XLA arm) reads nothing."""

import pytest

import run as bench_run

DIMS = {"layers": 36, "width": 1280}      # GPT-2-large: 184,320 B a token


def ctx(kernel_seconds, calls, live_kv_token_steps=384_000, trace=True):
    class Trace:
        def op_seconds(self, name):
            return {"flash_decode": (kernel_seconds, calls)}.get(
                name, (0.0, 0))
    return {"trace": Trace() if trace else None,
            "peaks": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9},
            "counters": {"dims": DIMS, "traced": {
                "decode_steps": 76,
                "live_kv_token_steps": live_kv_token_steps}}}


def test_share_is_live_kv_bytes_over_peak_over_kernel_time():
    reader, spec = bench_run.load_reader("flash_decode_hbm_roofline")
    assert spec["unit"] == "%" and spec["moves"] == "serve_tok_s"
    # 384,000 token-steps x 36 layers x 2 x 1280 x 2 B = 70.78 GB: 86.4 ms
    # at 819 GB/s, against 0.36 s of kernel time
    least = 384_000 * 36 * 2 * 1280 * 2 / 819e9
    assert reader(ctx(0.36, 76 * 36)) == pytest.approx(100 * least / 0.36)
    assert reader(ctx(0.36, 76 * 36)) == pytest.approx(24.0, abs=0.05)


@pytest.mark.parametrize("context", [
    ctx(0.0, 0),                                  # no such kernel: the parent
    ctx(0.36, 10, trace=False),                   # an untraced run
    ctx(0.36, 10, live_kv_token_steps=0),         # no decode step traced
], ids=["no_kernel", "no_trace", "no_decode_steps"])
def test_reads_nothing_where_there_is_nothing_to_read(context):
    reader, _spec = bench_run.load_reader("flash_decode_hbm_roofline")
    assert reader(context) is None
