"""Device time a step inside collectives (ms): the union of the all-reduce,
all-gather, all-to-all, reduce-scatter and collective-permute ops of the
busiest chip in the traced window, over its step programs
(``chipbench/trace.py:reduce``, ``collective_ms_per_step``).  It counts an
op from its start to its end on that chip, so it holds the wait for the
slowest chip as well as the transfer.  A trace with no collective in it (one
chip) reads 0.0; a run with no device trace reports nothing.  The divisor
is ``reduce``'s count of step programs in the window, which on four v5e
chips holds one ``jit_step`` event of 0.45 us too many (PERF.md section 7):
until that count is repaired this reads 14/15 of the ops' sum a whole step,
as the ``store.*_device_ms`` scopes do."""


def read(ctx):
    trace = ctx["trace"]
    return trace["collective_ms_per_step"] if trace else None
