"""Kernel microbenchmark: fused BFP matmul roofline terms per
(variant x shape), plus interpret-mode correctness spot check and measured
CPU wall time of the XLA dataflow.

``--smoke`` runs just one interpret-mode shape (CI compile-only gate)."""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import get_format
from repro.core.quantize import quantize
from repro.distributed.sharding import lane_shard_qtensor
from repro.kernels import ops, ref
from repro.kernels.bfp_matmul import bfp_matmul_pallas
from repro.launch.compile_cache import enable_compile_cache
from benchmarks.common import emit, time_jitted

PEAK = 197e12
HBM = 819e9

SHAPES = [
    ("decode", 8, 2048, 8192),
    ("prefill", 2048, 2048, 8192),
    ("train_fwd", 8192, 8192, 29568),
]


def smoke() -> None:
    """One kernel shape through the interpret-mode Pallas path; asserts
    against the oracle. Cheap enough for a CPU-only CI job."""
    M, K, N = 16, 512, 128
    x = jax.random.normal(jax.random.PRNGKey(0), (M, K))
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N)) * 0.1
    t = quantize("q3_k", w)
    o_ref = np.asarray(ref.matmul_ref(x, t))
    o_pal = np.asarray(bfp_matmul_pallas(
        x, t, interpret=True, compute_dtype=jnp.float32,
        out_dtype=jnp.float32, block_m=16, block_n=128, block_k=256))
    err = np.abs(o_pal - o_ref).max() / (np.abs(o_ref).max() + 1e-9)
    assert err < 1e-5, err
    emit("kernel_smoke_q3_k", 0.0, f"pallas_vs_ref_rel_err={err:.2e}")

    # fused sliced-TP gemm: each lane shard's packed payload goes
    # straight through the fused dequant-matmul and must reproduce the
    # matching columns of the full-matrix run BIT-exactly (packing runs
    # along K, so lane slicing never crosses a quantization group --
    # this is the invariant the sliced serving datapath rides on)
    shards = 2
    worst = 0.0
    for i in range(shards):
        tl = lane_shard_qtensor(t, i, shards)
        o_sh = np.asarray(bfp_matmul_pallas(
            x, tl, interpret=True, compute_dtype=jnp.float32,
            out_dtype=jnp.float32, block_m=16, block_n=64, block_k=256))
        n = N // shards
        worst = max(worst, np.abs(o_sh - o_pal[:, i*n:(i+1)*n]).max())
    assert worst == 0.0, worst
    emit("kernel_smoke_sliced_q3_k", 0.0,
         f"shard_vs_full_maxabs={worst:.1e} shards={shards}")


def run() -> None:
    for v in ("q2_k", "q3_k", "q4_k", "q6_k"):
        fmt = get_format(v)
        for name, M, K, N in SHAPES:
            flops = 2 * M * K * N
            w_bytes = fmt.nbytes(K, N)
            io = M * K * 2 + M * N * 4
            t_c = flops / PEAK
            t_m = (w_bytes + io) / HBM
            t_m_bf16 = (K * N * 2 + io) / HBM
            bound = "compute" if t_c > t_m else "memory"
            emit(f"kernel_{v}_{name}", max(t_c, t_m) * 1e6,
                 f"v5e_{bound}-bound mem_vs_bf16={t_m_bf16/t_m:.2f}x "
                 f"ai={flops/(w_bytes+io):.0f}")

    # correctness spot check (interpret kernel vs oracle) + CPU wall time
    M, K, N = 16, 1024, 512
    x = jax.random.normal(jax.random.PRNGKey(0), (M, K))
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N)) * 0.1
    for v in ("q2_k", "q3_k"):
        t = quantize(v, w)
        o_ref = np.asarray(ref.matmul_ref(x, t))
        o_pal = np.asarray(bfp_matmul_pallas(
            x, t, interpret=True, compute_dtype=jnp.float32,
            out_dtype=jnp.float32, block_m=16, block_n=128, block_k=256))
        err = np.abs(o_pal - o_ref).max() / (np.abs(o_ref).max() + 1e-9)
        f = jax.jit(lambda xx, tt: ops.bfp_matmul(xx, tt, impl="xla"))
        wall = time_jitted(f, x, t)
        emit(f"kernel_validate_{v}", wall * 1e6,
             f"pallas_vs_ref_rel_err={err:.2e}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        smoke()
    else:
        run()
