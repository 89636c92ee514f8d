"""Live lanes of a step's keys over all of them (%): how much of what a
step of ragged key bags pulls, permutes and pushes names a row at all (the
rest is a short bag's padding, which the store gathers clipped and drops).
From the program's own counters, the gauges ``bag_live_keys`` and
``bag_padded_keys`` that ``StreamingDriver`` sets from the last dispatch's
outputs once the loop has ended (counted on the device from the logic's
lane mask; no fetch inside the window).  A program without them (every
logic whose keys are all live, the parent) reports nothing."""


def read(ctx):
    try:
        from flink_parameter_server_tpu.telemetry.registry import get_registry
    except ImportError:
        return None
    gauges = get_registry().snapshot()

    def value(name):
        entries = gauges.get(name) or [{}]
        return entries[0].get("value")

    live, padded = value("bag_live_keys"), value("bag_padded_keys")
    return 100.0 * live / padded if live and padded else None
