"""Kernel layer: each Pallas kernel's share of its roofline. The least time
the chips could take for the work the algorithm needs in the traced steps
(harness/work.py; all chips' work over all chips' peak) over the kernel's
device time, found in the trace by the kernel's stable name and averaged over
the chips. A reader whose kernel is not in the trace returns nothing."""

from harness import work


def _share(ctx, kernel, need):
    c = ctx["counters"]
    steps = c.get("traced_steps")
    if not steps or ctx["trace"] is None:
        return None
    seconds, count = ctx["trace"].op_seconds(kernel)
    if count == 0 or seconds <= 0:
        return None
    pk = ctx["peaks"]
    least = work.roofline_seconds(
        need["flops"] * steps / c["chips"], need["bytes"] * steps / c["chips"],
        pk["flops_bf16"], pk["hbm_bytes_s"])
    return 100.0 * least["seconds"] / seconds


def _attention_shape(ctx):
    d, tr = ctx["counters"]["dims"], ctx["traffic"]
    return dict(batch=tr["global_batch"], heads=d["heads"],
                seq=tr["seq_len"], head_dim=d["head_dim"])


def flash_attention_fwd_roofline(ctx):
    need = work.flash_attention_fwd(**_attention_shape(ctx))
    n = ctx["counters"]["dims"]["layers"]
    return _share(ctx, "flash_attention_fwd",
                  {k: v * n for k, v in need.items()})


def flash_attention_bwd_roofline(ctx):
    need = work.flash_attention_bwd(**_attention_shape(ctx))
    n = ctx["counters"]["dims"]["layers"]
    return _share(ctx, "flash_attention_bwd",
                  {k: v * n for k, v in need.items()})


def fused_adamw_ema_time_share(ctx):
    """Not a roofline: on the v5e the update's operands are staged into
    on-chip memory by async copies that run outside the kernel's own time,
    so bytes over that time read 183 % of the HBM peak (PR 26). What is
    left to say is how much of the step the kernel takes."""
    if ctx["trace"] is None:
        return None
    kernel, count = ctx["trace"].op_seconds("fused_adamw_ema")
    step, steps = ctx["trace"].module_seconds("jit_train_step")
    if count == 0 or steps == 0 or step <= 0:
        return None
    return 100.0 * kernel / step
