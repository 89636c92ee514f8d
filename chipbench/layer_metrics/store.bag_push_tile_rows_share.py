"""Tile rows of eight rows a rule store's tile write-back read and wrote over
the distinct rows it rewrote (%): what the largest layer of the multi-hot
cell's step is made of (``ps.push``, ``core/store._push_rule``'s
``tile_assign`` arm on a flat row of two registers: every touched tile of
eight rows is read, set and written once, two DMA descriptors a tile row), so
lower is better (100: every rewritten row opens a tile row of its own; 12.5:
every tile row is rewritten whole).  From the program's own counters, the
gauges ``store_rule_tiles`` and ``store_rule_rows`` that ``StreamingDriver``
sets from the last dispatch's outputs once the loop has ended (counted on the
device from the kernel's plan; no fetch inside the window).
``store.push_tile_rows_share`` reads the ``add`` store's tile kernel
(``store_push_tile_rows`` / ``store_push_kernel_lanes``), which a rule store's
push never sets, so it has nothing to hand over here.  A program without the
gauges (an ``add`` store, the parent), and one whose write-back XLA's ``set``
took (``store_rule_tiles`` 0), reports nothing."""


def read(ctx):
    try:
        from flink_parameter_server_tpu.telemetry.registry import get_registry
    except ImportError:
        return None
    gauges = get_registry().snapshot()

    def value(name):
        entries = gauges.get(name) or [{}]
        return entries[0].get("value")

    tiles, rows = value("store_rule_tiles"), value("store_rule_rows")
    return 100.0 * tiles / rows if tiles and rows else None
