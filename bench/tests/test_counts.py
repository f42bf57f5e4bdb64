"""FLOP and byte counts against values worked out by hand for one
projection of each configuration, and against the program's own format
table."""
import json

import pytest

from conftest import BENCH
from harness import spec
from harness.counts import Counts
from harness.formats import packed_bytes


def conf(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_packed_bytes_by_hand():
    # qwen3 wq: q3_k, K = N = 2048: qs K*N/4, hmask K*N/8, scales K*N/16,
    # d one float16 per 256 rows
    assert packed_bytes("q3_k", 2048, 2048) == (
        1048576 + 524288 + 262144 + 8 * 2048 * 2)
    # phi3 wk: q2_k, K = N = 3072: qs K*N/4, scales K*N/16, d and dmin
    assert packed_bytes("q2_k", 3072, 3072) == (
        2359296 + 589824 + 2 * 12 * 3072 * 2)
    # the LM heads at their unpadded vocabularies
    assert packed_bytes("q2_k", 2048, 151936) == 2048 * 151936 * 21 // 64
    assert packed_bytes("q2_k", 3072, 32064) == 3072 * 32064 * 21 // 64


@pytest.mark.parametrize("variant", ["q2_k", "q3_k"])
@pytest.mark.parametrize("K,N", [(2048, 6144), (8192, 3072), (3072, 32064)])
def test_packed_bytes_match_program_formats(variant, K, N):
    from repro.core.formats import get_format

    assert packed_bytes(variant, K, N) == get_format(variant).nbytes(K, N)


def test_projection_table_qwen3():
    c = conf("qwen3-1.7b")
    fam = spec.load_module(BENCH / "families" / "dense.py", "dense_t")
    got = {p: (K, N, v, n) for p, K, N, v, n in fam.projections(c)}
    assert got == {
        "layers/attn/wq": (2048, 2048, "q3_k", 28),
        "layers/attn/wk": (2048, 1024, "q2_k", 28),
        "layers/attn/wv": (2048, 1024, "q2_k", 28),
        "layers/attn/wo": (2048, 2048, "q3_k", 28),
        "layers/mlp/w_gate": (2048, 6144, "q3_k", 28),
        "layers/mlp/w_up": (2048, 6144, "q3_k", 28),
        "layers/mlp/w_down": (6144, 2048, "q3_k", 28),
        "lm_head": (2048, 151936, "q2_k", 1),
    }


def test_model_flops_by_hand():
    fam = spec.load_module(BENCH / "families" / "dense.py", "dense_t")
    # phi3-mini-3.8b's published widths on the dense family
    phi3 = dict(conf("qwen3-1.7b"), num_hidden_layers=32, hidden_size=3072,
                num_attention_heads=32, num_key_value_heads=32, head_dim=96,
                intermediate_size=8192, vocab_size=32064, qk_norm=False)
    c = Counts(fam, phi3)
    layer = 4 * 3072 * 3072 + 3 * 3072 * 8192          # attn + mlp
    assert c.layer_params == 32 * layer
    assert c.head_params == 3072 * 32064
    pair = 4 * 32 * 32 * 96                              # 4 L H Dh
    assert c.decode_flops(100) == 2 * (32 * layer + 3072 * 32064) + 100 * pair
    # a 3-token prompt: 3 rows through the layers, 1+2+3 causal pairs,
    # the head on its last token
    assert c.prefill_flops(3) == (2 * 32 * layer * 3 + 2 * 3072 * 32064
                                  + 6 * pair)


def test_least_time_is_the_larger_bound():
    f, bw = 197e12, 819e9
    wbytes = packed_bytes("q3_k", 2048, 6144)
    mm = [(2048, 6144, wbytes)]
    # one row: bound by the packed bytes
    assert Counts.least_s(mm, 1, f, bw) == pytest.approx(
        (wbytes + (2048 + 6144) * 2) / bw)
    # 512 rows: bound by the FLOPs
    assert Counts.least_s(mm, 512, f, bw) == pytest.approx(
        2 * 512 * 2048 * 6144 / f)
