"""Whole-step share of the chip's peak: the operations the step's mathematics
requires (harness/work.py) over the traced window's wall time and the chips'
bf16 peak. It bounds every kernel roofline that moves the same metric: a
later PR that takes a kernel off the path leaves that kernel's roofline
silent, and can claim a gain only while this still rises."""

from harness import work


def step_mfu_train(ctx):
    c, tr = ctx["counters"], ctx["traffic"]
    if not c.get("traced_steps") or not c.get("traced_seconds"):
        return None
    per_token = work.train_flops_per_token(
        c["n_params"], c["dims"]["layers"], c["dims"]["width"],
        tr["seq_len"])
    tok_s = c["traced_steps"] * c["tokens_per_step"] / c["traced_seconds"]
    return 100.0 * per_token * tok_s / (
        c["chips"] * ctx["peaks"]["flops_bf16"])


def step_mfu_serve(ctx):
    c = ctx["counters"]
    t = c.get("traced") or {}
    if not t.get("t"):
        return None
    processed = t["prompt_tokens"] + t["slot_steps_active"]
    return 100.0 * work.decode_flops_per_token(c["n_params"]) * processed / (
        t["t"] * ctx["peaks"]["flops_bf16"])
