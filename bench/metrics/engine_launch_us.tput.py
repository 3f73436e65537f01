"""Mean wall microseconds of the engine's ``serving/launch`` spans over the
traced window: the host's launch of one group's program, which returns
before the device has run it."""

import spans


def read(ctx):
    return spans.mean_us("serving/launch")
