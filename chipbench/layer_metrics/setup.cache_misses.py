"""Programs set-up compiled because the persistent cache did not hold them
(count): the compile ledger's ``cache_miss`` events (the program's
``compile_cache_misses_total``) before the window.  0 on a warm run; above 0
this run's other ``setup.*`` values are a compiling run's."""
from chipbench import setup_ledger


def read(ctx):
    return setup_ledger.count_before_window(ctx, ("cache_miss",))
