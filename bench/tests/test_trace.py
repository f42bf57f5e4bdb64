"""The trace reduction on a small recorded-by-hand trace (an XSpace
built from text): busy union, idle share, program and kernel time, and
what the host was doing in each idle gap."""
import pytest

from harness import tracing

# times in ps inside lines whose timestamp_ns is 1000
XSPACE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 11 offset_ps: 6000000 duration_ps: 3000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 20 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 21 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 21 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 20 offset_ps: 7000000 duration_ps: 2000000 } }
  event_metadata { key: 10 value { id: 10 name: "jit__prefill_chunk_impl(3)" } }
  event_metadata { key: 11 value { id: 11 name: "jit__decode_chunk_impl(4)" } }
  event_metadata { key: 20 value { id: 20 name: "%fusion.7 = (f32[8]{0:T(128)}, bf16[2,8]{1,0:T(2,128)(2,1)S(1)}) fusion(bf16[8] %p), kind=kLoop" } }
  event_metadata { key: 21 value { id: 21 name: "%closed_call.2 = f32[8,128]{1,0:T(8,128)S(1)} custom-call(bf16[8,256]{1,0} %x), custom_call_target=\\"tpu_custom_call\\"" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 3 name: "python" timestamp_ns: 1000
    events { metadata_id: 30 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 31 offset_ps: 3000000 duration_ps: 3000000 }
    events { metadata_id: 32 offset_ps: 3500000 duration_ps: 2500000 } }
  event_metadata { key: 30 value { id: 30 name: "bench.window" } }
  event_metadata { key: 31 value { id: 31 name: "bench.poll" } }
  event_metadata { key: 32 value { id: 32 name: "bench.wait_arrival" } }
}
'''


@pytest.fixture(scope="module")
def prof():
    from jax.profiler import ProfileData

    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return tracing.from_profile(pd, 1)


def test_window_and_busy_union(prof):
    dev = prof.devices[0]
    assert prof.window == (1000.0, 11000.0)
    # ops cover [0,3) and [6,9) us of the 10 us window: overlap counted once
    assert tracing.busy_s(dev, prof.window) == pytest.approx(6e-6)
    assert tracing.idle_gaps(dev, prof.window) == [(4000.0, 7000.0),
                                                   (10000.0, 11000.0)]


def test_program_and_kernel_time(prof):
    dev, w = prof.devices[0], prof.window
    assert tracing.module_s(dev, w, "_prefill_chunk_impl") == pytest.approx(4e-6)
    assert tracing.module_s(dev, w, "_decode_chunk_impl") == pytest.approx(3e-6)
    assert tracing.op_s(dev, w, "custom-call", "_prefill_chunk_impl") == \
        pytest.approx(2e-6)
    assert tracing.op_s(dev, w, "custom-call", "_decode_chunk_impl") == \
        pytest.approx(1e-6)
    assert [e.name for e in dev.ops] == ["fusion", "closed_call",
                                        "closed_call", "fusion"]
    assert [e.module for e in dev.ops] == ["jit__prefill_chunk_impl"] * 2 + [
        "jit__decode_chunk_impl"] * 2


def test_clipping_to_the_window(prof):
    dev = prof.devices[0]
    assert tracing.busy_s(dev, (2500.0, 8000.0)) == pytest.approx(2.5e-6)


def test_idle_gaps_by_host_activity(prof):
    gaps = dict(tracing.top_gaps(prof, prof.devices[0]))
    # the 3 us gap lies mostly in the innermost span, the wait for an
    # arrival; the last 1 us in no span: the engine's own host code
    assert gaps == {"bench.wait_arrival": pytest.approx(3e-6),
                    "engine": pytest.approx(1e-6)}


def test_top_ops_name_instruction_opcode_and_program(prof):
    top = dict(tracing.top_ops(prof.devices[0], prof.window))
    assert top == {
        "jit__prefill_chunk_impl/fusion (fusion)": pytest.approx(2e-6),
        "jit__decode_chunk_impl/fusion (fusion)": pytest.approx(2e-6),
        "jit__prefill_chunk_impl/closed_call (custom-call)":
            pytest.approx(2e-6),
        "jit__decode_chunk_impl/closed_call (custom-call)":
            pytest.approx(1e-6)}
