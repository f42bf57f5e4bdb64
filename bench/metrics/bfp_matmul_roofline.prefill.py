"""Kernels (kernels/bfp_matmul.py) in the prefill programs: the least
time the chip needs for the packed projections of the prompts prefilled
in the traced window, over the device time of the bfp_matmul kernel
there, in percent. Per prefill group and projection the least time is
the larger of FLOPs over peak and bytes over bandwidth, with the live
prompt rows only, the stored packed bytes at the unpadded N read once,
and the LM head on one row per prompt. Moves ttft_p95_ms."""
from harness.layers import BFP_KERNEL, PREFILL_PROGRAM, share


def read(ctx):
    c, pk = ctx.counts, ctx.peaks
    f, bw = pk["bf16_flops"], pk["hbm_bytes_per_s"]
    least = sum(c.least_s(c.layer_mm, sum(g), f, bw)
                + c.least_s(c.head_mm, len(g), f, bw)
                for g in ctx.prefill_groups())
    t = ctx.kernel_s(BFP_KERNEL, PREFILL_PROGRAM)
    return share(least, t) if least else None
