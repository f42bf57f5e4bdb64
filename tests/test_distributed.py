"""Distribution tests: sharding rules, compressed gradient all-reduce,
and a subprocess tiny-mesh dry-run (the multi-pod config, miniaturized)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_arch
from repro.distributed import sharding as SH
from repro.models import transformer as T

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeMesh:
    """Axis-name/shape stand-in for spec tests (no devices needed)."""
    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)
        self.size = int(np.prod(list(shape.values())))


def test_param_specs_rules():
    mesh = FakeMesh({"data": 16, "model": 16})
    cfg = get_arch("llama3.2-1b")
    params = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    specs = SH.param_specs(params, mesh)
    lay = specs["layers"]
    assert lay["attn"]["wq"] == P(None, ("data",), "model")     # column
    assert lay["attn"]["wo"] == P(None, "model", ("data",))     # row
    assert lay["mlp"]["w_down"] == P(None, "model", ("data",))  # row
    assert lay["ln1"]["w"] == P()
    assert specs["wte"] == P("model", ("data",))


def test_param_specs_moe_ep_vs_tp():
    mesh = FakeMesh({"data": 16, "model": 16})
    # olmoe: 64 experts % 16 == 0 -> EP over model
    specs = SH.param_specs(jax.eval_shape(
        lambda: T.init_params(get_arch("olmoe-1b-7b"),
                              jax.random.PRNGKey(0))), mesh)
    assert specs["layers"]["moe"]["w_gate"][1] == "model"
    # granite: 40 % 16 != 0 -> per-expert FFN TP
    specs2 = SH.param_specs(jax.eval_shape(
        lambda: T.init_params(get_arch("granite-moe-3b-a800m"),
                              jax.random.PRNGKey(0))), mesh)
    g = specs2["layers"]["moe"]["w_gate"]
    assert g[1] is None and g[3] == "model"


def test_qtensor_specs_row_vs_column():
    from repro.core.policy import get_policy
    from repro.core.qlinear import spec_like_quantized
    mesh = FakeMesh({"data": 16, "model": 16})
    cfg = get_arch("llama3.2-1b")
    sds = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    qsds = spec_like_quantized(sds, get_policy("default_serve_mix"))
    specs = SH.param_specs(qsds, mesh, fsdp=False)
    # column-parallel wq: lanes over model
    assert specs["layers"]["attn"]["wq"].data["qs"] == P(None, None, "model")
    # row-parallel w_down (K=8192 SB-aligned for 16): rows over model
    assert specs["layers"]["mlp"]["w_down"].data["qs"] == P(None, "model",
                                                            None)


def test_cache_specs_adaptive():
    mesh = FakeMesh({"data": 16, "model": 16})
    # kv=8 not divisible by 16 -> flash-decoding sequence sharding
    # (head_dim mode is never auto-chosen: GSPMD re-gathers the cache,
    # see EXPERIMENTS.md §Perf H1)
    cfg = get_arch("qwen2-vl-72b")
    cache = jax.eval_shape(lambda: T.init_cache(cfg, 128, 1024))
    specs = SH.cache_specs(cache, mesh)
    assert specs["k"] == P(None, ("data",), "model", None, None)  # seq
    cfg2 = get_arch("phi3-mini-3.8b")  # kv=32 divisible
    cache2 = jax.eval_shape(lambda: T.init_cache(cfg2, 128, 1024))
    specs2 = SH.cache_specs(cache2, mesh)
    assert specs2["k"] == P(None, ("data",), None, "model", None)  # heads
    # B=1 long-context: sequence shards over dp
    cache3 = jax.eval_shape(lambda: T.init_cache(cfg2, 1, 2048))
    specs3 = SH.cache_specs(cache3, mesh)
    # PartitionSpec may normalize 1-tuples to the bare axis name
    assert specs3["k"][1] is None
    assert specs3["k"][2] in ("data", ("data",))


def test_nested_and_reentrant_role_contexts():
    """tp_off / activation_axes are stacked contexts: nesting tp_off
    inside an active activation_axes, re-entering the SAME context
    object, and sequential reuse must all restore state exactly
    (regression: the old per-instance ``_saved`` slot was clobbered on
    re-entry, leaving the module-level role dicts corrupted)."""
    mesh = FakeMesh({"data": 2, "model": 2})
    assert SH.model_axis(mesh) == "model"

    # nested tp_off inside activation_axes: the inner context must not
    # disturb the outer's activation frame on exit
    with SH.activation_axes(mesh):
        assert SH._ACT_STACK[-1]["enabled"]
        outer_frame = dict(SH._ACT_STACK[-1])
        with SH.tp_off():
            assert SH.model_axis(mesh) is None
            assert SH.dp_axes(mesh) == ("data", "model")
            assert SH._ACT_STACK[-1] == outer_frame       # untouched
        assert SH.model_axis(mesh) == "model"             # tp restored
        assert SH._ACT_STACK[-1] == outer_frame
    assert not SH._ACT_STACK[-1]["enabled"]
    assert len(SH._ACT_STACK) == 1 and len(SH._TP_STACK) == 1

    # re-entering the SAME context object (the old code restored the
    # inner snapshot and left tp permanently off)
    ctx = SH.tp_off()
    with ctx:
        with ctx:
            assert SH.model_axis(mesh) is None
        assert SH.model_axis(mesh) is None                # outer active
    assert SH.model_axis(mesh) == "model"
    assert len(SH._TP_STACK) == 1

    # sequential reuse of one activation_axes object stays balanced
    act = SH.activation_axes(mesh)
    for _ in range(2):
        with act:
            assert SH._ACT_STACK[-1]["enabled"]
        assert not SH._ACT_STACK[-1]["enabled"]
    assert len(SH._ACT_STACK) == 1

    # interleaved (out-of-order) exits still converge to a clean base
    a, b = SH.tp_off(), SH.tp_off()
    a.__enter__()
    b.__enter__()
    a.__exit__(None, None, None)                          # out of order
    b.__exit__(None, None, None)
    assert len(SH._TP_STACK) == 1 and SH.model_axis(mesh) == "model"

    with pytest.raises(RuntimeError, match="without matching"):
        SH.tp_off().__exit__(None, None, None)


def test_constrain_noop_outside_context():
    x = jnp.ones((4, 4))
    assert SH.constrain(x, "dp", None) is x


def test_compressed_psum_error_feedback():
    """bf16-wire all-reduce with error feedback on a real 1-device mesh."""
    from repro.distributed.compress import compressed_psum
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    g = {"w": jnp.asarray([[1.0004883, -2.0], [0.5, 3.141592]])}
    red, err = compressed_psum(g, mesh)
    # single device: reduced == bf16(g); error = g - bf16(g)
    np.testing.assert_allclose(
        np.asarray(red["w"]),
        np.asarray(g["w"].astype(jnp.bfloat16).astype(jnp.float32)))
    total = np.asarray(red["w"]) + np.asarray(err["w"])
    np.testing.assert_allclose(total, np.asarray(g["w"]), rtol=1e-6)
    # second step: residual is carried
    red2, err2 = compressed_psum(g, mesh, error=err)
    total2 = np.asarray(red2["w"]) + np.asarray(err2["w"])
    np.testing.assert_allclose(total2, np.asarray(g["w"]) * 1
                               + np.asarray(err["w"]), rtol=1e-5)


DRYRUN_SNIPPET = r"""
import os
os.environ["REPRO_DRYRUN_DEVICES"] = "16"
import sys
sys.path.insert(0, "src")
from repro.launch import dryrun as D
import jax
# miniature production mesh pair: (4,4) and multi-pod (2,2,4)
for axes, shape in ((("data","model"), (4,4)),
                    (("pod","data","model"), (2,2,4))):
    mesh = jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
    rec = D.dryrun_cell("llama3.2-1b", "decode_32k", mesh=mesh)
    assert rec["status"] == "ok", rec
    assert rec["memory"]["total_hbm_bytes"] > 0
    assert rec["roofline"]["dominant"] in ("compute", "memory",
                                           "collective")
print("SUBPROCESS_DRYRUN_OK")
"""


@pytest.mark.slow
def test_subprocess_tiny_mesh_dryrun():
    out = subprocess.run([sys.executable, "-c", DRYRUN_SNIPPET], cwd=REPO,
                         capture_output=True, text=True, timeout=900)
    assert "SUBPROCESS_DRYRUN_OK" in out.stdout, out.stdout + out.stderr


def test_collective_parser():
    from repro.launch.analysis import collective_bytes, shape_bytes
    hlo = """
  %cvt = f32[8,16]{1,0} convert(%x)
  %dot.1 = f32[8,16]{1,0} dot(%cvt, %convert_bitcast_fusion.2)
  %all-reduce.1 = f32[8,16]{1,0} all-reduce(%dot.1), replica_groups={}
  %ag = bf16[4,8]{1,0} all-gather(%y), replica_groups={}
  %rs-start = f32[16]{0} reduce-scatter-start(%z)
"""
    out = collective_bytes(hlo)
    assert out["all-reduce"] == 8 * 16 * 4
    assert out["all-gather"] == 4 * 8 * 2
    assert out["reduce-scatter"] == 16 * 4
    # the f32 all-reduce fed by a promoted bf16 dot counts at bf16 width
    assert out["total_corrected"] == 8 * 16 * 2 + 4 * 8 * 2 + 16 * 4
    assert shape_bytes("(f32[2,3], bf16[4])") == 24 + 8
