"""Layers of the Keye-VL-2.0 stage: the whole step and the prefill program
against the chip's bf16 peak, the decode program against the memory roofline,
what the selection leaves of a full cache's reads, what tile padding and
touched-but-foreign tiles leave of the grouped expert pass's rows, and the two
prefill kernels at THIS model's head counts and widths. Work is counted by
harness/work_keye_vl2.py from the program's own counters, read as
readers/deepseek_v32.py reads them (the ``serve.fetch`` spans' arguments, by
the program that counted).

A program without the grouped pass's counters (a commit before them, another
family) reads as nothing."""

from harness import work_keye_vl2 as work

from readers import deepseek_v32 as base
from readers.deepseek_v32 import (ATTEND_KERNEL, DECODE_PROGRAM,
                                  INDEX_KERNEL, PREFILL_PROGRAM)

ROWS = "expert_rows_computed"
GROUPED_KERNEL = "grouped_expert_matmul"   # ops/grouped_matmul.py's name


def _counted(program):
    c = base.counted(program)
    return c if c is not None and ROWS in c else None


def step_mfu_serve_sparse_gqa(ctx):
    """Needed FLOPs of everything the traced window processed (prompt
    tokens through the layers, decode slot-steps through layers and head)
    over its wall time and the peak."""
    t, pre, dec = base._traced(ctx), _counted("prefill"), _counted("decode")
    if t is None or pre is None or dec is None:
        return None
    cfg = ctx["config"]
    need = (work.flops_needed(cfg, tokens=t["prompt_tokens"],
                              head_tokens=0, counted=pre)
            + work.flops_needed(cfg, tokens=t["slot_steps_active"],
                                head_tokens=t["slot_steps_active"],
                                counted=dec))
    return 100.0 * need / (t["t"] * ctx["peaks"]["flops_bf16"])


def prefill_mfu_serve_sparse_gqa(ctx):
    t, pre = base._traced(ctx), _counted("prefill")
    if t is None or pre is None or ctx["trace"] is None:
        return None
    seconds, count = ctx["trace"].module_seconds(PREFILL_PROGRAM)
    if count == 0 or seconds <= 0:
        return None
    need = work.flops_needed(ctx["config"], tokens=t["prompt_tokens"],
                             head_tokens=0, counted=pre)
    return 100.0 * need / (seconds * ctx["peaks"]["flops_bf16"])


def decode_hbm_roofline_sparse_gqa(ctx):
    t, dec = base._traced(ctx), _counted("decode")
    if t is None or dec is None or not t.get("decode_steps") \
            or ctx["trace"] is None:
        return None
    seconds, count = ctx["trace"].module_seconds(DECODE_PROGRAM)
    if count == 0 or seconds <= 0:
        return None
    need = work.decode_bytes_needed(ctx["config"], steps=t["decode_steps"],
                                    counted=dec)
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_s"]) / seconds


def attended_kv_share_sparse_gqa(ctx):
    """K/V rows attention read over the rows that were live, prefill chunks
    and decode steps together."""
    pre, dec = _counted("prefill"), _counted("decode")
    if pre is None or dec is None:
        return None
    live = pre["kv_rows_live"] + dec["kv_rows_live"]
    if live <= 0:
        return None
    return 100.0 * (pre["kv_rows_attended"] + dec["kv_rows_attended"]) / live


def expert_rows_needed_share(ctx):
    """Routed assignments over the rows the grouped products multiplied,
    prefill chunks and decode steps together: what tile padding and the
    tiles a group only touches leave of the grouped pass's rows."""
    pre, dec = _counted("prefill"), _counted("decode")
    if pre is None or dec is None:
        return None
    rows = pre[ROWS] + dec[ROWS]
    if rows <= 0:
        return None
    return 100.0 * (pre["expert_assignments_held"]
                    + dec["expert_assignments_held"]) / rows


def mla_block_attend_roofline_sparse_gqa(ctx):
    """The prefill attention kernel at 32 query heads on 4 key heads: the
    least time the chip could take for the (query, key) pairs the chunks'
    attention had to compute (counter) over the kernel's device time."""
    pre, n = _counted("prefill"), base._chunk_tokens(ctx)
    return base._kernel_share(ctx, ATTEND_KERNEL, pre and n and (
        work.prefill_attention_needed(
            ctx["config"], attended_rows=pre["kv_rows_attended"],
            chunk_tokens=n)))


def lightning_index_scores_roofline_sparse_gqa(ctx):
    """The prefill's indexer kernel at 16 heads of 64, likewise, for the
    pairs it had to score."""
    pre, n = _counted("prefill"), base._chunk_tokens(ctx)
    return base._kernel_share(ctx, INDEX_KERNEL, pre and n and (
        work.index_scores_needed(
            ctx["config"], scored_rows=pre["index_rows_scored"],
            chunk_tokens=n)))


def grouped_expert_matmul_roofline(ctx):
    """The expert layer's kernel, prefill chunks and decode steps together:
    the least time the chip could take for the assignments' three products
    and the touched experts' matrices (counters) over the kernel's device
    time."""
    pre, dec = _counted("prefill"), _counted("decode")
    if pre is None or dec is None:
        return None
    return base._kernel_share(ctx, GROUPED_KERNEL, (
        work.grouped_experts_needed(
            ctx["config"],
            assignments=pre["expert_assignments_held"]
            + dec["expert_assignments_held"],
            experts_touched=pre["experts_touched"]
            + dec["experts_touched"])))
