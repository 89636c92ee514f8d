"""Host seconds inside the store's construction on the device (s): the
program's counter ``setup_store_place_seconds_total``, fed by its span
``setup.store_place`` round ``ShardedParamStore.create`` / ``from_values`` /
``from_spec_values`` (the placement's programs traced, loaded and enqueued;
nothing there waits for the device)."""
from chipbench import setup_ledger


def read(ctx):
    return setup_ledger.counter_total("setup_store_place_seconds_total")
