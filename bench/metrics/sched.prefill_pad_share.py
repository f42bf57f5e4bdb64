"""Scheduler (serving/engine.py admission): the share of the positions
prefilled in the traced window that were padding (the group's longest
prompt rounded to its bucket, the group rounded to a power of two),
1 - prefill_tokens / prefill_padded_tokens of ``Engine.stats``. Moves
ttft_p95_ms. None where the engine has no such counter."""


def read(ctx):
    if "prefill_padded_tokens" not in ctx.stats1:
        return None
    n = ctx.delta("prefill_padded_tokens")
    return 1.0 - ctx.delta("prefill_tokens") / n if n > 0 else None
