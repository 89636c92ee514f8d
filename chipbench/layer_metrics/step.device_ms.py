"""Median device duration of the jitted step program (ms), on the busiest
chip.  From the device trace."""


def read(ctx):
    return ctx["trace"] and ctx["trace"]["step_device_ms"]
