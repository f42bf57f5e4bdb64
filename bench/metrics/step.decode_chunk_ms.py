"""Model step (the ``decode_step`` loop of one decode chunk): ms per
chunk over the traced window, decode_s / chunks of ``Engine.stats``.
Moves tpot_p95_ms."""


def read(ctx):
    n = ctx.delta("chunks")
    return ctx.delta("decode_s") / n * 1e3 if n > 0 else None
