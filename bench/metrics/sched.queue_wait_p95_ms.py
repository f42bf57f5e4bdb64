"""Scheduler (serving/engine.py admission): 95th percentile, in ms, of
how long the requests admitted in the traced window waited from when
they were due to the start of their prefill group (``Request.queue_wait_s``,
a host timer of the program). Moves ttft_p95_ms."""
import numpy as np


def read(ctx):
    waits = [r.queue_wait_s for r in ctx.admitted()]
    return float(np.percentile(waits, 95)) * 1e3 if waits else None
