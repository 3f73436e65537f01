"""Mean wait from submit to the dispatcher's pop, from the engine's
``serving_queue_seconds`` histogram over the window."""


def read(ctx):
    c = ctx["record"]["counters"]
    if not c["queue_waits"]:
        return None
    return c["queue_wait_s"] / c["queue_waits"] * 1e3
