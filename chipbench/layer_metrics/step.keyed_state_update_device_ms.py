"""Device time a step of the ops under ``ps.state_pull`` and
``ps.state_push`` (ms) where the worker state is partitioned over keyed
workers: each worker's gather from, and row-kernel update of, its own block
of the user factors, for its own lane block of the microbatch, on the
busiest chip.  (``step.state_update_device_ms`` is the same reading in the
one-worker MF cells.)"""
from chipbench import program_trace


def read(ctx):
    return program_trace.scope_ms(ctx, "ps.state_pull", "ps.state_push")
