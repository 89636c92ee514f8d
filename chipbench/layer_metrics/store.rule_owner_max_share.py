"""The fullest shard's share of the distinct rows a step's rule rewrote (%):
how far the partition of a sharded rule store is from even.  With ``ps`` = 4
servers 25 is even; cell 12's contiguous blocks give shard 1 32.4 % of a
batch's 355.4 k distinct rows (the configuration's ``assumed.partitioning``),
and a step waits for that shard's rule and write-back.  From the program's
own counters, the gauges ``store_rule_rows_max_shard`` and
``store_rule_rows`` that ``StreamingDriver`` sets from the last dispatch's
outputs once the loop has ended (no fetch inside the window).  A program
without them (the parent; a store in one place) reports nothing."""


def gauge(name):
    """The program's gauge ``name``, or ``None`` where it set none."""
    try:
        from flink_parameter_server_tpu.telemetry.registry import get_registry
    except ImportError:
        return None
    entries = get_registry().snapshot().get(name) or [{}]
    return entries[0].get("value")


def read(ctx):
    most, rows = gauge("store_rule_rows_max_shard"), gauge("store_rule_rows")
    return 100.0 * most / rows if most and rows else None
