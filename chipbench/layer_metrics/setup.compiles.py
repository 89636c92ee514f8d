"""Backend compiles of set-up, loads from the persistent cache included, all
programs (count): the compile ledger's ``backend`` events (the program's
``xla_compiles_total{program=}``) that began before the window.  It is the
harness's ``compiles_total`` less what it compiles after the window; a
re-built ``jax.jit`` of the step adds 2, a new eager helper 1."""
from chipbench import setup_ledger


def read(ctx):
    return setup_ledger.count_before_window(ctx, ("backend",))
