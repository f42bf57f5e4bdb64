"""The system under test, set up from a cell: the program's ModelConfig
for the configuration file, the benchmark's weights in the program's
parameter types, the Engine with the cell's serve settings, and the
warm-up of exactly the program shapes the cell's traffic reaches."""
from __future__ import annotations

from typing import Dict, List, Set, Tuple

import jax

from harness.formats import Packed


def model_config(cell):
    from repro.configs.base import get_arch

    fam = cell.family()
    mc = get_arch(cell.config["arch"]).replace(
        **fam.model_overrides(cell.config))
    fam.check_model(cell.config, mc)
    return mc


def program_params(weights):
    """The benchmark's weight tree with each packed leaf wrapped in the
    program's QTensor; the device arrays are shared, not copied."""
    from repro.core.quantize import QTensor

    return jax.tree.map(
        lambda w: (QTensor(w.variant, (w.K, w.N), dict(w.arrays))
                   if isinstance(w, Packed) else w),
        weights, is_leaf=lambda x: isinstance(x, Packed))


def make_engine(mc, params, cell, seed: int):
    from repro.serving.engine import Engine, ServeConfig

    return Engine(mc, params, ServeConfig(seed=seed, **cell.serve))


def prefill_shapes(serve: Dict, prompt_lens: List[int]
                   ) -> Set[Tuple[int, int]]:
    """(group, chunk) prefill programs this traffic can reach: groups pad
    to powers of two up to prefill_batch; a group's chunk is its longest
    prompt rounded up to prefill_bucket, or prefill_chunk past that."""
    b, C = serve.get("prefill_bucket", 16), serve["prefill_chunk"]
    chunks = {min(-(-n // b) * b, C) for n in prompt_lens}
    top = min(serve["prefill_batch"], serve["max_slots"])
    groups = {min(1 << (n - 1).bit_length(), serve["prefill_batch"])
              for n in range(1, top + 1)}
    return {(g, c) for g in groups for c in chunks}


def warm_up(eng, shapes: Set[Tuple[int, int]], vocab: int) -> None:
    """Run every (group, chunk) prefill program and the decode chunk once,
    through the engine's own calls, so nothing compiles in the window."""
    for g, c in sorted(shapes):
        for i in range(g):
            eng.submit([(7 * i + j) % vocab for j in range(c)],
                       max_new_tokens=2)
        eng.run()
