"""Device layer: how much of the traced window no operation ran."""


def idle_share(ctx):
    s = ctx["trace"]
    if s is None or s.window_s <= 0 or s.busy_s <= 0:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
