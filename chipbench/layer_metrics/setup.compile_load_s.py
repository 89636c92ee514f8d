"""Host seconds set-up spent in XLA compiles and loads from the persistent
cache, all programs (s): the compile ledger's ``backend`` events before the
window (the program's records ``compile.backend.<program>``, its
``xla_compile_seconds_total``).  Loads on a warm run, compiles on a first."""
from chipbench import setup_ledger


def read(ctx):
    return setup_ledger.seconds_before_window(ctx, ("backend",))
