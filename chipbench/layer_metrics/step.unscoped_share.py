"""Share of the step programs' device time under no ``ps.*`` scope (%): what
the four scoped metrics leave out, holes between a program's ops included
(``chipbench/program_trace.py``).  Nothing without a scoped op."""
from chipbench import program_trace


def read(ctx):
    share = (program_trace.of_run(ctx) or {}).get("unscoped_share")
    return None if share is None else 100.0 * share
