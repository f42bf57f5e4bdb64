"""A configuration, a traffic mix, a cell and a per-layer metric added as
files are found by name, with no edit to the harness."""
import json

from harness import spec


def test_new_parts_load_by_name(tiny_tree):
    root, bench = tiny_tree
    (bench / "metrics" / "sched.new_counter.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["per_layer"].append({"name": "sched.new_counter", "unit": "count",
                            "better": "lower", "source": "program_counter",
                            "layer": "scheduler", "moves": "ttft_p95_ms",
                            "workloads": ["tiny.chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))

    cell = spec.load_cell("tiny.chat", root, bench)
    assert cell.config["hidden_size"] == 256
    assert cell.traffic["prompt"]["median"] == 40
    assert cell.serve["max_slots"] == 4
    assert cell.family().dims(cell.config)["Dh"] == 64
    names = [m["name"] for m in cell.per_layer]
    assert "sched.new_counter" in names
    assert cell.metric_reader("sched.new_counter")(None) == 42.0
    # a metric listed for other cells only is not this cell's
    other = spec.load_cell("qwen3-1.7b.chat", root, bench)
    assert "sched.new_counter" not in [m["name"] for m in other.per_layer]


def test_every_cell_finds_its_files():
    bm = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    for w in bm["workloads"]:
        cell = spec.load_cell(w["name"])
        for m in cell.per_layer:
            assert callable(cell.metric_reader(m["name"]))
        assert cell.settings["rate_rps"] > 0
        assert [e["name"] for e in cell.end_to_end][-1] == "setup_s"


def test_unknown_device_kind_is_an_error():
    import pytest

    assert spec.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("cpu")


def test_program_config_matches_each_file():
    from harness import model

    bm = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    for w in bm["workloads"]:
        cell = spec.load_cell(w["name"])
        mc = model.model_config(cell)          # raises on any difference
        assert mc.kernel_impl == "pallas"
    assert model.model_config(spec.load_cell("qwen3-1.7b.chat")).norm_eps \
        == 1e-6
