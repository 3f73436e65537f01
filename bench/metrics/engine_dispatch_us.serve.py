"""Mean wall microseconds of the engine's ``serving/dispatch`` spans over the
traced window: one popped group on the dispatcher thread, from the pop to
its last ticket's completion."""

import spans


def read(ctx):
    return spans.mean_us("serving/dispatch")
