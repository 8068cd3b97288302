"""Kernel layer, serving: the flash-decode kernel (``ops/flash_decode.py``)
against the memory roofline of the live K and V it has to read. Found in the
trace by the kernel's stable name; a program that holds no such kernel (the
XLA decode arm, a model with a decode step of its own) reads nothing."""

from harness import work

KERNEL = "flash_decode"


def flash_decode_hbm_roofline(ctx):
    """Live K/V bytes of the traced decode steps (the scheduler's host
    mirrors: the term ``decode_hbm_roofline`` counts, the same whatever
    implements the step) over the HBM peak, against the kernel's summed
    device time."""
    c = ctx["counters"]
    t = c.get("traced") or {}
    if not t.get("live_kv_token_steps") or ctx["trace"] is None:
        return None
    seconds, count = ctx["trace"].op_seconds(KERNEL)
    if count == 0 or seconds <= 0:
        return None
    need = t["live_kv_token_steps"] * work.kv_bytes_per_token(
        c["dims"]["layers"], c["dims"]["width"])
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_s"]) / seconds
