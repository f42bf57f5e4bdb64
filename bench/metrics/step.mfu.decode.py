"""Model step, decode: model FLOPs of the tokens decoded in the traced
window (every layer and the LM head per token, attention over each
query's live context only) over the device time of the decode programs
times the chip's peak, in percent. Moves tpot_p95_ms."""
from harness.layers import DECODE_PROGRAM, share


def read(ctx):
    flops = sum(ctx.counts.decode_flops(c) for c in ctx.decode_contexts())
    t = ctx.program_s(DECODE_PROGRAM)
    return share(flops / ctx.peaks["bf16_flops"], t) if flops else None
