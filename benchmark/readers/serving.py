"""Serving layers: the decode program against the memory roofline, and how
full the scheduler keeps the compiled decode batch."""

from harness import work

DECODE_PROGRAM = "jit_decode_fn"   # jit name of DecodeEngine's decode step


def decode_hbm_roofline(ctx):
    """Bytes a decode step must read (every weight once in the compute type,
    bfloat16, and the K and V of every live token, counted from the
    scheduler's host mirrors) over the HBM peak, against the decode
    program's device time."""
    c = ctx["counters"]
    t = c.get("traced") or {}
    if not t.get("decode_steps") or ctx["trace"] is None:
        return None
    seconds, count = ctx["trace"].module_seconds(DECODE_PROGRAM)
    if count == 0 or seconds <= 0:
        return None
    need = (t["decode_steps"] * 2.0 * c["n_params"]
            + t["live_kv_token_steps"] * work.kv_bytes_per_token(
                c["dims"]["layers"], c["dims"]["width"]))
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_s"]) / seconds


def batch_occupancy(ctx):
    c = ctx["counters"]
    t = c.get("traced") or {}
    if not t.get("decode_steps"):
        return None
    return 100.0 * t["slot_steps_active"] / (t["decode_steps"] * c["slots"])
