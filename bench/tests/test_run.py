"""A whole run on the CPU at a tiny size, past the harness's look for a
chip (Pallas in interpret mode): sound, it is correct; with the timed
path broken underneath, ``correct`` comes out false. And the command
itself refuses to run without a TPU, or without the program."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax

from conftest import BENCH, ROOT
from harness import runner


def tiny_run(root, bench, seed, **kw):
    return runner.run("tiny.chat", seed, 3.0, False, time.perf_counter(),
                      root=root, bench_dir=bench,
                      chips_ok=lambda n: jax.devices()[:n],
                      kind="TPU v5 lite", **kw)


def test_sound_run_is_correct(tiny_tree, interpret_pallas):
    root, bench = tiny_tree
    res = tiny_run(root, bench, 2 ** 31 + 77)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms",
                                   "output_tok_s", "peak_hbm_gb", "setup_s"}
    assert res["checks"]["max_logit_gap"]["value"] <= 0.05


def test_altered_token_is_not_correct(tiny_tree, interpret_pallas,
                                      monkeypatch):
    from repro.serving.engine import Engine

    sample = Engine._sample

    def altered(self, logits, key):
        tok = sample(self, logits, key)
        return tok.at[0].set((tok[0] + 1) % logits.shape[-1])

    monkeypatch.setattr(Engine, "_sample", altered)
    root, bench = tiny_tree
    res = tiny_run(root, bench, 5)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > 0.05


def test_dropped_answers_are_not_correct(tiny_tree, interpret_pallas,
                                         monkeypatch):
    """Half of the work left out: the second half of every decode chunk's
    tokens never reaches the client, so no request that decodes past
    half a chunk is answered in full."""
    from repro.serving.engine import Engine

    emit = Engine._emit_chunk

    def half(self, out):
        out = out.copy()
        out[:, out.shape[1] // 2:] = -1
        return emit(self, out)

    monkeypatch.setattr(Engine, "_emit_chunk", half)
    root, bench = tiny_tree
    cell = json.loads((bench / "cells" / "tiny.chat.json").read_text())
    cell["drain_s"] = 3.0
    (bench / "cells" / "tiny.chat.json").write_text(json.dumps(cell))
    res = tiny_run(root, bench, 6)
    assert not res["correct"] and res["failed"] > 0


def test_control_reads_far_above_the_program(tiny_tree, interpret_pallas):
    """The control (the float8 reference in the program's place) at a
    test size: its widest gap lies well above the program's and above the
    tiny cell's limit, which the program's stays under, so the cell's own
    verdict finds the control not correct and the program correct."""
    root, bench = tiny_tree
    ses = runner.Session("tiny.chat", root=root, bench_dir=bench,
                         chips_ok=lambda n: jax.devices()[:n],
                         kind="TPU v5 lite")
    prog, ctrl = [], []
    for seed in (11, 12, 13):
        res = ses.run(seed, 3.0, False, time.perf_counter(),
                      keep_engine=True, control=True)
        assert res["correct"] is True and res["control_correct"] is False
        prog.append(res["checks"]["max_logit_gap"]["value"])
        ctrl.append(res["control_max_logit_gap"])
    assert max(prog) <= 0.05 < min(ctrl)
    assert min(ctrl) >= 3 * max(prog)


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-1.7b.chat",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_refuses_without_a_tpu():
    p = _cli(ROOT)
    assert p.returncode != 0 and p.stdout == ""


def test_cli_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0 and p.stdout == ""
