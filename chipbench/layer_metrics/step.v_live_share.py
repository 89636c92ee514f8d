"""Keys of a step whose EMBEDDING the gate let through, over its live keys
(%): how much of a gated factorisation machine's interaction term runs at
all (DiFacto: a feature's ``V`` counts once its count passes ``V_threshold``
and its weight is not zero).  From the program's own counters, the gauges
``fm_v_live_keys`` and ``fm_live_keys`` that ``StreamingDriver`` sets from the
last dispatch's outputs once the loop has ended (counted on the device from
the logic's own masks; no fetch inside the window).  A program without them
(every logic without a gate, the parent) reports nothing."""


def read(ctx):
    try:
        from flink_parameter_server_tpu.telemetry.registry import get_registry
    except ImportError:
        return None
    gauges = get_registry().snapshot()

    def value(name):
        entries = gauges.get(name) or [{}]
        return entries[0].get("value")

    gated, live = value("fm_v_live_keys"), value("fm_live_keys")
    return 100.0 * gated / live if gated is not None and live else None
