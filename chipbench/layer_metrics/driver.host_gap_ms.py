"""Median idle gap on the device between two consecutive step programs
(ms): what the host adds between dispatches.  From the device trace."""


def read(ctx):
    return ctx["trace"] and ctx["trace"]["host_gap_ms"]
