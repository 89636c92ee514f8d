"""Tile rows of eight rows the push's tile kernel read and wrote over the
lanes it kept (%): what the kernel's time is made of, two DMA descriptors a
tile row against an add a lane, so lower is better (100: every lane opens a
tile row of its own; a batch of duplicates and neighbours reads far lower).
From the program's own counters, the gauges ``store_push_tile_rows`` and
``store_push_kernel_lanes`` that ``StreamingDriver`` sets from the last
dispatch's outputs once the loop has ended (counted on the device from the
kernel's plan; no fetch inside the window).  A program whose push XLA's
scatter-add takes, and one without the gauges (the parent), reports
nothing."""


def read(ctx):
    try:
        from flink_parameter_server_tpu.telemetry.registry import get_registry
    except ImportError:
        return None
    gauges = get_registry().snapshot()

    def value(name):
        entries = gauges.get(name) or [{}]
        return entries[0].get("value")

    tile_rows = value("store_push_tile_rows")
    lanes = value("store_push_kernel_lanes")
    return 100.0 * tile_rows / lanes if tile_rows and lanes else None
