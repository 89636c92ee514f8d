"""Host seconds set-up spent tracing and lowering, all programs (s): the
compile ledger's ``trace`` and ``lower`` events before the window (the
program's records ``compile.trace.<program>`` and ``compile.lower.<program>``,
its ``jit_trace_seconds_total`` and ``jit_lower_seconds_total``).  No cache
saves them: every run pays them for every program it dispatches."""
from chipbench import setup_ledger


def read(ctx):
    return setup_ledger.seconds_before_window(ctx, ("trace", "lower"))
