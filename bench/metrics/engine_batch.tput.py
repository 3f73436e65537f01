"""Requests per dispatch over the window: the engine's ``completed`` over
its ``dispatches`` counter."""


def read(ctx):
    c = ctx["record"]["counters"]
    if not c["dispatches"]:
        return None
    return c["completed"] / c["dispatches"]
