"""Model step (``decode_step``'s KV ring): the share of the ring
positions the decode steps of the traced window read that held live
context, decode_ctx_positions / decode_ring_positions of
``Engine.stats``; every step reads the whole ring in every slot. Moves
tpot_p95_ms. None where the engine has no such counters."""


def read(ctx):
    if "decode_ring_positions" not in ctx.stats1:
        return None
    n = ctx.delta("decode_ring_positions")
    return ctx.delta("decode_ctx_positions") / n if n > 0 else None
