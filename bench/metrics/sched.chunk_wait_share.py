"""Scheduler (serving/engine.py admission): the share of the queue wait
of the requests admitted in the traced window that passed before the
engine received them (due -> ``submit()``, in which the decode chunk in
flight ends and the poll comes round), pre_enqueue_wait_s /
queue_wait_total_s of ``Engine.stats``; the rest is waiting in the
engine's queue for a slot or a group. Moves ttft_p95_ms. None where the
engine has no such sums."""


def read(ctx):
    if "pre_enqueue_wait_s" not in ctx.stats1:
        return None
    n = ctx.delta("queue_wait_total_s")
    return ctx.delta("pre_enqueue_wait_s") / n if n > 0 else None
