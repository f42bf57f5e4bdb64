"""Kernels (kernels/bfp_matmul.py) in the decode programs: the least
time the chip needs for the packed projections of the tokens decoded in
the traced window, over the device time of the bfp_matmul kernel there,
in percent. Per decode step and projection the least time is the larger
of FLOPs over peak and bytes over bandwidth, with the live slots only
(averaged over a chunk's steps, which can only lower the count) and the
stored packed bytes at the unpadded N read once a step. Moves
tpot_p95_ms."""
from harness.layers import BFP_KERNEL, DECODE_PROGRAM, share


def read(ctx):
    c, pk = ctx.counts, ctx.peaks
    f, bw = pk["bf16_flops"], pk["hbm_bytes_per_s"]
    least = sum(steps * c.least_s(c.layer_mm + c.head_mm, rows / steps,
                                  f, bw)
                for steps, rows in ctx.decode_chunks())
    t = ctx.kernel_s(BFP_KERNEL, DECODE_PROGRAM)
    return share(least, t) if least else None
