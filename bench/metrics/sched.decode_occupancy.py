"""Scheduler (serving/engine.py decode loop): the share of the decode
slot-steps run in the traced window that emitted a token,
decode_tokens / decode_slot_steps of ``Engine.stats`` (a chunk runs
as many steps as its busiest slot emits tokens, each over every slot).
Moves tpot_p95_ms. None where the engine has no such counters."""


def read(ctx):
    if "decode_slot_steps" not in ctx.stats1:
        return None
    n = ctx.delta("decode_slot_steps")
    return ctx.delta("decode_tokens") / n if n > 0 else None
