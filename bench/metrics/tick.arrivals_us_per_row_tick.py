"""Device time of the tick stage ``tick.arrivals`` (the arrivals (enqueue)
stage) per simulated row-tick in the traced chunk (us), averaged over the
chips: its ops' innermost time (``bench/stage_trace.py``) over the row-ticks
``tick.device_us_per_row_tick`` divides by.  Nothing to read where no op of
the stage ran."""
from bench import stage_trace


def read(ctx):
    return stage_trace.us_per_row_tick(ctx, "tick.arrivals")
