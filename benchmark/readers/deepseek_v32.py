"""Layers of the DeepSeek-V3.2-Exp share: the whole step and the prefill
program against the chip's bf16 peak, the decode program against the memory
roofline, and how sparse attention really was. Work is counted by
harness/work_deepseek_v32.py from the program's own counters, which
``DecodeServer`` books as arguments of the ``serve.fetch`` spans it closes in
the traced sub-window (by the program that counted: ``prefill`` / ``decode``).

A program without those counters (a commit before them, another family)
reads as nothing."""

from harness import work_deepseek_v32 as work

from harness.work import roofline_seconds

ATTEND_KERNEL = "mla_block_attend"         # ops/mla_attention.py's names
INDEX_KERNEL = "lightning_index_scores"
PREFILL_PROGRAM = "jit_prefill_chunk_fn"   # DecodeEngine's jit names
DECODE_PROGRAM = "jit_decode_fn"


def counted(program):
    """Sums of the ring's counters for one program, or None."""
    try:
        from distributed_pipeline_tpu.obs import trace
        events = trace.recorded()
    except (ImportError, AttributeError):
        return None
    total = {}
    for e in events:
        group = (e.get("args") or {}).get(program) \
            if e.get("name") == "serve.fetch" else None
        for k, v in (group or {}).items():
            total[k] = total.get(k, 0) + v
    return total or None


def _traced(ctx):
    t = ctx["counters"].get("traced") or {}
    return t if t.get("t") else None


def step_mfu_serve_routed(ctx):
    """Needed FLOPs of everything the traced window processed (prompt
    tokens through the layers, decode slot-steps through layers and head)
    over its wall time and the peak."""
    t, pre, dec = _traced(ctx), counted("prefill"), counted("decode")
    if t is None or pre is None or dec is None:
        return None
    cfg = ctx["config"]
    need = (work.flops_needed(cfg, tokens=t["prompt_tokens"],
                              head_tokens=0, counted=pre)
            + work.flops_needed(cfg, tokens=t["slot_steps_active"],
                                head_tokens=t["slot_steps_active"],
                                counted=dec))
    return 100.0 * need / (t["t"] * ctx["peaks"]["flops_bf16"])


def prefill_mfu_serve(ctx):
    t, pre = _traced(ctx), counted("prefill")
    if t is None or pre is None or ctx["trace"] is None:
        return None
    seconds, count = ctx["trace"].module_seconds(PREFILL_PROGRAM)
    if count == 0 or seconds <= 0:
        return None
    need = work.flops_needed(ctx["config"], tokens=t["prompt_tokens"],
                             head_tokens=0, counted=pre)
    return 100.0 * need / (seconds * ctx["peaks"]["flops_bf16"])


def decode_hbm_roofline_sparse_latent(ctx):
    t, dec = _traced(ctx), counted("decode")
    if t is None or dec is None or not t.get("decode_steps") \
            or ctx["trace"] is None:
        return None
    seconds, count = ctx["trace"].module_seconds(DECODE_PROGRAM)
    if count == 0 or seconds <= 0:
        return None
    need = work.decode_bytes_needed(ctx["config"], steps=t["decode_steps"],
                                    counted=dec)
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_s"]) / seconds


def attended_kv_share(ctx):
    """Latent rows attention read over the rows that were live, prefill
    chunks and decode steps together."""
    pre, dec = counted("prefill"), counted("decode")
    if pre is None or dec is None:
        return None
    live = pre["kv_rows_live"] + dec["kv_rows_live"]
    if live <= 0:
        return None
    return 100.0 * (pre["kv_rows_attended"] + dec["kv_rows_attended"]) / live


def _kernel_share(ctx, kernel, need):
    if need is None or ctx["trace"] is None:
        return None
    seconds, count = ctx["trace"].op_seconds(kernel)
    if count == 0 or seconds <= 0:
        return None
    least = roofline_seconds(need["flops"], need["bytes"],
                             ctx["peaks"]["flops_bf16"],
                             ctx["peaks"]["hbm_bytes_s"])
    return 100.0 * least["seconds"] / seconds


def _chunk_tokens(ctx):
    t = _traced(ctx)
    if t is None or not t.get("prefill_steps"):
        return None
    return t["prompt_tokens"] / t["prefill_steps"]


def mla_block_attend_roofline(ctx):
    """The prefill attention kernel: the least time the chip could take for
    the (query, key) pairs the chunks' attention had to compute (counter)
    over the kernel's device time."""
    pre, n = counted("prefill"), _chunk_tokens(ctx)
    return _kernel_share(ctx, ATTEND_KERNEL, pre and n and (
        work.prefill_attention_needed(
            ctx["config"], attended_rows=pre["kv_rows_attended"],
            chunk_tokens=n)))


def lightning_index_scores_roofline(ctx):
    """The prefill's indexer kernel, likewise, for the pairs it had to
    score."""
    pre, n = counted("prefill"), _chunk_tokens(ctx)
    return _kernel_share(ctx, INDEX_KERNEL, pre and n and (
        work.index_scores_needed(
            ctx["config"], scored_rows=pre["index_rows_scored"],
            chunk_tokens=n)))
