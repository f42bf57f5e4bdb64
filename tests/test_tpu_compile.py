"""The serving path's Pallas kernels compile for a TPU v5e.

Interpret mode (test_kernels.py, test_prefill_attn.py) checks numerics
but not what Mosaic, the Pallas TPU compiler, accepts: legal block tiles,
vector casts and loads, VMEM. The TPU compiler is installed with jaxlib,
so these tests compile for a described v5e chip that is not attached.
Nothing runs; a compile that passes is not a chip run.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_arch
from repro.core.formats import WEIGHT_VARIANTS
from repro.core.quantize import QTensor, qtensor_spec
from repro.kernels.bfp_matmul import bfp_matmul_pallas
from repro.kernels.prefill_attn import prefill_attn_fused
from repro.models import transformer as T

# (K, N) of every tinyllama-1.1b projection: wq/wo, wk/wv, w_gate/w_up,
# w_down, lm_head
TINYLLAMA_KN = [(2048, 2048), (2048, 256), (2048, 5632), (5632, 2048),
                (2048, 32000)]
PAPER = ("q2_k", "q3_k")
CASES = ([(v, 8, K, N) for v in PAPER for K, N in TINYLLAMA_KN]
         + [(v, 512, 2048, 5632) for v in WEIGHT_VARIANTS
            if v not in PAPER])


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip; keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("variant,M,K,N", CASES)
def test_bfp_matmul_compiles_for_v5e(one_chip, variant, M, K, N):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    spec = qtensor_spec(variant, K, N)
    assert spec.variant == variant
    t = QTensor(variant, (K, N),
                {k: sds(a.shape, a.dtype) for k, a in spec.data.items()})
    _compile(bfp_matmul_pallas, sds((M, K), jnp.bfloat16), t)


def test_prefill_attn_fused_compiles_for_v5e(one_chip):
    # tinyllama prefill: 8 rows x 64-token chunk, 32 heads over 4 kv
    # heads, head dim 64, against a 1024-slot ring plus the chunk
    B, C, H, KH, D, T = 8, 64, 32, 4, 64, 1024 + 64
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    _compile(prefill_attn_fused, sds((B, C, H, D), jnp.bfloat16),
             sds((B, T, KH, D), jnp.bfloat16),
             sds((B, T, KH, D), jnp.bfloat16), sds((B, C), jnp.int32),
             sds((B, T), jnp.int32))


def test_decode_step_never_copies_the_ring_for_v5e(one_chip):
    """The decode step writes each token's K/V into the stacked ring in
    place and reads its layer where it lies (the ``decode_attn`` kernel):
    at qwen3-1.7b's per-layer shapes (32 slots, a 2048-position ring, 8 kv
    heads x 128) no instruction makes or writes a whole per-layer ring."""
    cfg = get_arch("qwen3-1.7b").replace(n_layers=3, vocab_size=1024)
    B, Tn = 32, 2048
    on = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = on(jax.eval_shape(lambda: T.init_params(
        cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)))
    cache = on(jax.eval_shape(lambda: T.init_cache(cfg, B, Tn)))
    vec = lambda dt: jax.ShapeDtypeStruct((B,), dt, sharding=one_chip)

    def step(p, c, tok, pos, live):
        return T.decode_step(p, cfg, c, tokens=tok, position=pos, live=live)

    text = jax.jit(step, donate_argnums=1).lower(
        params, cache, vec(jnp.int32), vec(jnp.int32),
        vec(jnp.bool_)).compile().as_text()
    assert re.search(r"%decode_attn[.\d]* = .*custom-call", text)
    ring = re.compile(r"bf16\[(1,)?32,2048,8,128\]")
    shapes = dict(re.findall(r"%(\S+) = (\w+\[[\d,]*\])", text))
    made = [n for n, sh in shapes.items() if ring.fullmatch(sh)]
    assert not made, made
    for upd in re.findall(r"dynamic-update-slice\(%\S+, %([^,)]+)", text):
        assert not ring.fullmatch(shapes.get(upd, "")), upd
