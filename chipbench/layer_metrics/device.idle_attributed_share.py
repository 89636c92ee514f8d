"""Share of the device's idle time between programs that some ``fps.*`` span
of the program covers (%): how much of the idling the program's own spans can
name (``chipbench/program_trace.py``; its table says which span, a thread)."""
from chipbench import program_trace


def read(ctx):
    share = (program_trace.of_run(ctx) or {}).get("idle_attributed_share")
    return None if share is None else 100.0 * share
