"""Model step (``prefill_chunk``): microseconds of admission per prompt
token over the traced window, prefill_s / prefill_tokens of
``Engine.stats``. Moves ttft_p95_ms."""


def read(ctx):
    n = ctx.delta("prefill_tokens")
    return ctx.delta("prefill_s") / n * 1e6 if n > 0 else None
