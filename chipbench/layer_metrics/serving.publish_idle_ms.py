"""Device idle time under ``fps.train.publish``, a publish (ms): what a
snapshot publish costs the device beyond its copy
(``chipbench/program_trace.py``)."""
from chipbench import program_trace


def read(ctx):
    return (program_trace.of_run(ctx) or {}).get("publish_idle_ms")
