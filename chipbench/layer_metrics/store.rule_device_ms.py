"""Device time a step of the ops under the scope ``ps.rule`` (ms): inside the
push of a store whose update is a rule and not ``add``, the read of the
distinct rows' current values and the rule run on them; what is left under
``ps.push`` is then the write-back alone.  A program without that scope (an
``add`` store, the parent) reports nothing."""
from chipbench import program_trace


def read(ctx):
    return program_trace.scope_ms(ctx, "ps.rule")
