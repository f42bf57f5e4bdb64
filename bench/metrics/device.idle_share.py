"""Device: the share of the traced window in which no op ran on the
chip, 1 - (union of the device's op intervals) / (window). Moves
tpot_p95_ms."""


def read(ctx):
    w = ctx.window_s()
    return 1.0 - ctx.busy_s() / w if w > 0 else None
