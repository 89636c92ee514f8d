"""Live sampled lanes of a step over all of them (%): how many of the
neighbours a step draws in its three hops exist (805,000 lanes at 1,000 seeds
and fan-outs 15 / 10 / 5; a node with no neighbour in the share has DEAD
lanes below it, which pull row 0 and count in no mean).  From the program's
own counters, the gauges ``sage_live_lanes`` and ``sage_sampled_lanes`` that
``StreamingDriver`` sets from the last dispatch's outputs once the loop has
ended (counted on the device from the sampler's masks; no fetch inside the
window).  A program without them reports nothing."""


def read(ctx):
    try:
        from flink_parameter_server_tpu.telemetry.registry import get_registry
    except ImportError:
        return None
    gauges = get_registry().snapshot()

    def value(name):
        entries = gauges.get(name) or [{}]
        return entries[0].get("value")

    live, lanes = value("sage_live_lanes"), value("sage_sampled_lanes")
    return 100.0 * live / lanes if live and lanes else None
