"""Live records over all lanes of the keyed microbatches (%): what the
program's keyed shuffle (``data/keyed.KeyedRouter``) emitted as records over
that plus the lanes it padded because a worker's block ran short, from its
counters ``keyed_records`` and ``keyed_padded_lanes``.  100 where every
block of every microbatch was full.  A program without the router (the
parent) reports nothing."""


def read(ctx):
    try:
        from flink_parameter_server_tpu.telemetry.registry import get_registry
    except ImportError:
        return None
    counters = get_registry().snapshot()

    def value(name):
        entries = counters.get(name) or [{}]
        return entries[0].get("value")

    records, padded = value("keyed_records"), value("keyed_padded_lanes")
    if not records or padded is None:
        return None
    return 100.0 * records / (records + padded)
