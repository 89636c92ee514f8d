"""Seconds set-up waited for the Pallas import (s): the program's counter
``setup_kernel_import_seconds_total``, fed where the first kernel to be
traced asks for the modules (its ``setup.kernel_import_wait``): what is left
of the import that ``preload`` began on a thread beside the staging, or all
of it where nothing preloaded.  The thread's own length is a span only: it
overlaps the staging and is no part of ``setup_s``.  A cell whose step traces
no kernel reports nothing."""
from chipbench import setup_ledger


def read(ctx):
    return setup_ledger.counter_total("setup_kernel_import_seconds_total")
