"""Compile a cell's programs at their real size for a described TPU v5e
(no chip needed) and print each one's ``memory_analysis()``: the weight
build, the largest prefill group, the decode chunk and the reference.

    JAX_PLATFORMS=cpu python3 bench/tools/compile_check.py --workload <cell>
"""
import argparse
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness import check, model, spec, traffic
    from repro.models import transformer as T

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    on = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)

    cell = spec.load_cell(args.workload)
    fam, conf, sv = cell.family(), cell.config, cell.serve
    mc = model.model_config(cell)
    key = on(jax.eval_shape(lambda: jax.random.PRNGKey(0)))

    def show(name, compiled):
        m = compiled.memory_analysis()
        gb = lambda b: f"{b / 1e9:.3f} GB"
        print(f"{name}: arguments {gb(m.argument_size_in_bytes)}, outputs "
              f"{gb(m.output_size_in_bytes)}, temporaries "
              f"{gb(m.temp_size_in_bytes)}, aliased "
              f"{gb(m.alias_size_in_bytes)}", flush=True)

    build = jax.jit(lambda k: fam._build(conf, k))
    show("build", build.lower(key).compile())
    wspec = on(jax.eval_shape(lambda k: fam._build(conf, k), key))
    pspec = model.program_params(wspec)
    eng = model.make_engine(mc, pspec, cell, 0)
    B, Tn, V = sv["max_slots"], sv["cache_len"], conf["vocab_size"]
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    G, C = sv["prefill_batch"], sv["prefill_chunk"]
    gcache = on(jax.eval_shape(lambda: T.init_cache(mc, G, Tn)))
    show(f"prefill chunk ({G}, {C})", eng._prefill_chunk.lower(
        pspec, gcache, i32(G, C), i32(), i32(G),
        jax.ShapeDtypeStruct((G, V), jnp.float32, sharding=one),
        i32(G)).compile())
    cache = on(jax.eval_shape(lambda: T.init_cache(mc, B, Tn)))
    show(f"decode chunk ({B} slots)", eng._decode_chunk.lower(
        pspec, cache, i32(B), i32(B),
        jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one), i32(B), i32(B),
        key).compile())
    longest = traffic.longest_context(cell.traffic)
    ref = check.Reference(fam, conf, -(-longest // 128) * 128,
                          cell.traffic["output"]["max"])
    show("reference", ref._gaps.lower(
        wspec, i32(ref.seq_len), i32(), i32(ref.n_out)).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main())
