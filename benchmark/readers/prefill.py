"""Model step, serving: the flax-backbone family's prefill program
(``DecodeEngine``'s ``prefill_fn``, one padded ``[rows, max_prompt_len]``
batch a dispatch) against the chip's bf16 peak, counting only the prompt
tokens the dispatches really carried. ``step_mfu.serve`` counts the same 2N a
token over the whole window; this one divides by the prefill program's own
device time, so it falls with every padded position and dummy row the program
computes. A model with a prefill program of another name (the chunked
family's ``jit_prefill_chunk_fn``) reads nothing."""

from harness import work

PREFILL_PROGRAM = "jit_prefill_fn"   # jit name of DecodeEngine's prefill


def prefill_mfu_serve_dense(ctx):
    c = ctx["counters"]
    t = c.get("traced") or {}
    if not t.get("prompt_tokens") or ctx["trace"] is None:
        return None
    seconds, count = ctx["trace"].module_seconds(PREFILL_PROGRAM)
    if count == 0 or seconds <= 0:
        return None
    need = work.decode_flops_per_token(c["n_params"]) * t["prompt_tokens"]
    return 100.0 * need / (seconds * ctx["peaks"]["flops_bf16"])
