"""Device time a step of the SERVER side of the step (ms) where a rule
store's push runs on the shards that own its rows
(``core/store._push_rule_on_shards``): the ops under ``ps.combine`` +
``ps.rule`` + ``ps.push`` on the chip where they take LONGEST.  Every chip
sorts all the batch's lanes, but each sums the keys, reads, rules and writes
the rows of its own block only, so the chips differ (shard 0 owns 56.9 % of
cell 12's keys, shard 2 3.7 %) and the step waits for the slowest: each
chip's plane is reduced by itself (``program_trace.reduce``, the sums
``store.rule_path_device_ms`` takes on the busiest chip) and the largest
sum is the reading.  A run with no device trace, or a program without
``ps.combine`` (an ``add`` store), reports nothing."""
import os

from chipbench import program_trace

SCOPES = ("ps.combine", "ps.rule", "ps.push")


def by_chip(ctx):
    """``[{scope: ms a step}]``, one entry a chip's plane, parsed once a
    process; ``None`` where the run recorded no device trace."""
    if not ctx["trace"]:
        return None
    from chipbench import run, spec

    where = os.path.join(
        run.OUT_DIR, "trace", f"{ctx['cfg']['name']}.{ctx['traffic']['name']}"
    )
    # beside the whole run's reduction: a reader's module is loaded anew
    # for every caller (``spec.metric_reader``), so a cache of its own
    # would parse the trace once for this metric and once for the roofline
    key = where + "#by_chip"
    if key not in program_trace._RUNS:
        try:
            trace = program_trace.read_xplane(program_trace.find_xplane(where))
        except FileNotFoundError:
            program_trace._RUNS[key] = None
        else:
            step = spec.family(ctx["cfg"]["family"]).STEP_PROGRAM
            program_trace._RUNS[key] = {"chips": [
                (program_trace.reduce({**trace, "devices": [p]}, step) or {})
                .get("scope_ms", {})
                for p in trace["devices"]
            ]}
    found = program_trace._RUNS[key]
    return found["chips"] if found else None


def read(ctx):
    sums = [
        sum(chip[s] for s in SCOPES if s in chip)
        for chip in by_chip(ctx) or [] if "ps.combine" in chip
    ]
    return max(sums) if sums else None
