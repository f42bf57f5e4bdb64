"""The per-layer metrics that read the engine's spans and counters in
``Engine.stats``: their formulas over the window's deltas, and None,
not an error, on an engine that has no such keys."""
import pytest

from harness import spec
from repro.serving.engine import Engine

NEW = ("sched.host_ms_per_chunk", "sched.decode_occupancy",
       "step.decode_ring_live_share", "sched.prefill_pad_share",
       "sched.chunk_wait_share")


class Ctx:
    def __init__(self, stats0, stats1):
        self.stats0, self.stats1 = stats0, stats1

    def delta(self, key):
        return self.stats1[key] - self.stats0[key]


@pytest.fixture(scope="module")
def readers():
    cell = spec.load_cell("qwen3-1.7b.chat")
    return {n: cell.metric_reader(n) for n in NEW}


def _stats(**kw):
    s = Engine._fresh_stats()
    s.update(kw)
    return s


def test_formulas_over_the_window(readers):
    s0 = _stats(chunks=10, poll_s=1.0, decode_dispatch_s=2.0,
                decode_emit_s=0.5, decode_tokens=100, decode_slot_steps=400,
                decode_ctx_positions=1000, decode_ring_positions=8000,
                prefill_tokens=50, prefill_padded_tokens=80,
                pre_enqueue_wait_s=3.0, queue_wait_total_s=4.0)
    s1 = _stats(chunks=30, poll_s=1.02, decode_dispatch_s=2.05,
                decode_emit_s=0.53, decode_tokens=400, decode_slot_steps=1000,
                decode_ctx_positions=3000, decode_ring_positions=24000,
                prefill_tokens=350, prefill_padded_tokens=480,
                pre_enqueue_wait_s=7.5, queue_wait_total_s=9.0)
    got = {n: r(Ctx(s0, s1)) for n, r in readers.items()}
    assert got == pytest.approx({
        "sched.host_ms_per_chunk": 0.1 / 20 * 1e3,
        "sched.decode_occupancy": 300 / 600,
        "step.decode_ring_live_share": 2000 / 16000,
        "sched.prefill_pad_share": 1 - 300 / 400,
        "sched.chunk_wait_share": 4.5 / 5.0})


def test_none_without_the_keys_or_the_work(readers):
    old = {k: v for k, v in _stats().items()
           if k in ("chunks", "prefill_tokens", "prefill_s", "decode_s")}
    for name, read in readers.items():
        assert read(Ctx(old, old)) is None, name
        assert read(Ctx(_stats(), _stats())) is None, name
