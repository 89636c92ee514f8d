"""The longest interval between two consecutive dispatch starts in the window
(ms), less the time inside ``train.hooks`` between them (the harness starts
and stops the profiler in its hook): a few step periods on a sound run,
seconds on a run that lost seconds.  The program explains such a gap itself,
as a ``train.dispatch_gap`` record and a warning on stderr."""
import bisect

from chipbench import dispatch_ledger


def read(ctx):
    found = dispatch_ledger.dispatches(ctx)
    if found is None or len(found) < 2:
        return None
    starts = [s["start"] for s in found]
    gaps = [b - a for a, b in zip(starts, starts[1:])]
    for s in ctx["spans"]:
        if s["name"] == "hooks" and s["component"] == "train":
            i = bisect.bisect_right(starts, s["start"]) - 1
            if 0 <= i < len(gaps):
                gaps[i] -= min(s["dur"], starts[i + 1] - s["start"])
    return max(gaps) * 1e3
