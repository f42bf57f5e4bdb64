"""Model step, prefill: model FLOPs of the prompts prefilled in the
traced window (every prompt token through the layers, causal attention
over the live prefix only, the LM head on the last token) over the device
time of the prefill programs times the chip's peak, in percent. Moves
ttft_p95_ms."""
from harness.layers import PREFILL_PROGRAM, share


def read(ctx):
    flops = sum(ctx.counts.prefill_flops(len(r.arrival.prompt))
                for r in ctx.admitted())
    t = ctx.program_s(PREFILL_PROGRAM)
    return share(flops / ctx.peaks["bf16_flops"], t) if flops else None
