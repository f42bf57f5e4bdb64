"""Scheduler (serving/engine.py decode loop): host milliseconds per
decode chunk in which the device has nothing queued, since the loop
syncs at every chunk: (decode_dispatch_s + decode_emit_s + poll_s) /
chunks of ``Engine.stats`` over the traced window, the
``engine.decode.dispatch``, ``engine.decode.emit`` and ``engine.poll``
spans. Moves tpot_p95_ms. None where the engine has no such spans."""

KEYS = ("decode_dispatch_s", "decode_emit_s", "poll_s")


def read(ctx):
    if not all(k in ctx.stats1 for k in KEYS):
        return None
    n = ctx.delta("chunks")
    return sum(ctx.delta(k) for k in KEYS) / n * 1e3 if n > 0 else None
