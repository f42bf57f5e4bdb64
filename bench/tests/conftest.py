"""CPU tests of the benchmark's own code: ``pytest bench/tests``."""
import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import pytest  # noqa: E402


def write_tiny_tree(root: Path) -> Path:
    """A copy of the benchmark with one more configuration, mix and cell,
    all tiny, added as files only: qwen3's layer pattern at d 256."""
    bench = root / "bench"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = json.loads((BENCH / "configs" / "qwen3-1.7b.json").read_text())
    conf.update(num_hidden_layers=2, hidden_size=256, num_attention_heads=4,
                num_key_value_heads=2, head_dim=64, intermediate_size=512,
                vocab_size=512)
    (bench / "configs" / "tiny.json").write_text(json.dumps(conf))
    mix = {"arrival": {"process": "poisson"},
           "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.6,
                      "min": 8, "max": 100},
           "output": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                      "min": 4, "max": 24},
           "prefix": None}
    (bench / "traffic" / "tiny.json").write_text(json.dumps(mix))
    cell = json.loads((BENCH / "cells" / "qwen3-1.7b.chat.json").read_text())
    cell["serve"].update(max_slots=4, cache_len=128, prefill_batch=2,
                         prefill_chunk=32)
    cell.update(rate_rps=3.0, preroll_s=1.0, drain_s=30.0)
    cell["check"] = {"min_tokens": 40, "limits": {"max_logit_gap": 0.05}}
    (bench / "cells" / "tiny.chat.json").write_text(json.dumps(cell))
    bm["configs"].append({"name": "tiny", "source": "test",
                          "file": "bench/configs/tiny.json", "reduced": [],
                          "why": "test"})
    bm["workloads"].append({"name": "tiny.chat", "config": "tiny",
                            "traffic": "tiny", "chips": 1, "why": "test"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny.chat")
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return bench


@pytest.fixture
def tiny_tree(tmp_path):
    return tmp_path, write_tiny_tree(tmp_path)


@pytest.fixture
def interpret_pallas(monkeypatch):
    """The engine's Pallas bfp_matmul in interpret mode (CPU)."""
    from repro.kernels import ops
    from repro.kernels.bfp_matmul import bfp_matmul_pallas

    monkeypatch.setattr(
        ops, "bfp_matmul_pallas",
        lambda *a, **k: bfp_matmul_pallas(*a, **dict(k, interpret=True)))
