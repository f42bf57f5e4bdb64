"""Scheduler: the share of the engine's stepping time spent admitting
(batched chunked prefill) rather than decoding, over the traced window:
prefill_s / (prefill_s + decode_s) of ``Engine.stats``, host timers that
end in a device sync. Moves tpot_p95_ms: decoders wait while a group
prefills."""


def read(ctx):
    p, d = ctx.delta("prefill_s"), ctx.delta("decode_s")
    return p / (p + d) if p + d > 0 else None
