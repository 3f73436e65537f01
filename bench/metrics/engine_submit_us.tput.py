"""Mean wall microseconds of the engine's ``serving/submit`` spans over the
traced window: the whole of ``ProjectionEngine.submit``, on the caller's
thread."""

import spans


def read(ctx):
    return spans.mean_us("serving/submit")
