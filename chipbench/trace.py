"""From a profiler trace to numbers — the one reduction every PR shares.

``read_xplane`` turns the ``.xplane.pb`` the JAX profiler writes into plain
data (planes -> lines -> ``[name, start_ns, dur_ns]`` events, only the lines
the reduction reads); ``reduce`` computes busy union, idle share, step
program and gap statistics, collective time and the breakdown from that
plain form, so it can be pinned on a small recorded trace
(``chipbench/fixtures/``) without a chip.

Device planes are ``/device:TPU:<n>``; their ``XLA Modules`` line holds one
event per executed program and ``XLA Ops`` one per HLO op.  Host threads are
lines of ``/host:CPU``; the benchmark's ``jax.profiler.TraceAnnotation``
spans (``chipbench.*``) land there on the same clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

from chipbench.stats import median

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
ANNOTATION_PREFIX = "chipbench."
WINDOW = "chipbench.window"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute"
)
Interval = Tuple[int, int]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path: str) -> List[dict]:
    """Planes as plain data.  Device planes keep their module and op lines;
    host planes keep only ``chipbench.*`` annotations; op names are cut
    to name and result shape."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name)
        lines = []
        for line in plane.lines:
            if device and line.name not in (MODULES_LINE, OPS_LINE):
                continue
            events = [
                [
                    _short(ev.name) if line.name == OPS_LINE else ev.name,
                    int(ev.start_ns), int(ev.duration_ns),
                ]
                for ev in line.events
                if device or ev.name.startswith(ANNOTATION_PREFIX)
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


def union(intervals: List[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(events, lo: int, hi: int) -> List[Interval]:
    return [
        (max(s, lo), min(s + d, hi)) for _, s, d in events
        if s + d > lo and s < hi
    ]


def _total(intervals: List[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


SHAPED = re.compile(r"^%?(\S+) = \(?([a-z0-9]+\[[0-9,]*\])")


def _short(name: str) -> str:
    """An op as the trace prints it, cut to its name and result shape:
    ``%fusion.2 = f32[5008260,128]{1,0:T(8,128)} fusion(...)`` becomes
    ``fusion.2 f32[5008260,128]``."""
    m = SHAPED.match(name)
    if m:
        return f"{m.group(1)} {m.group(2)}"
    return name.split(" = ")[0].strip().lstrip("%")[:80]


def reduce(planes: List[dict], step_program: str) -> Optional[dict]:
    """All the trace-borne numbers of one traced window; ``None`` when no
    operation ran on a device (there is then nothing to divide)."""
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    devices = [p for p in devices if _line(p, OPS_LINE)]
    if not devices:
        return None
    host = [
        ev for p in planes if not DEVICE_PLANE.match(p["name"])
        for line in p["lines"] for ev in line["events"]
    ]
    window = [ev for ev in host if ev[0] == WINDOW]
    if window:
        lo, hi = window[0][1], window[0][1] + window[0][2]
    else:  # no marker: the span of device activity
        every = [ev for p in devices for ev in _line(p, OPS_LINE)]
        lo = min(s for _, s, _ in every)
        hi = max(s + d for _, s, d in every)
    busy = {p["name"]: union(_clip(_line(p, OPS_LINE), lo, hi)) for p in devices}
    busiest = max(devices, key=lambda p: _total(busy[p["name"]]))
    busy_s = sum(_total(b) for b in busy.values()) / len(busy) / 1e9
    window_s = (hi - lo) / 1e9

    # step programs wholly inside the window, on the busiest chip
    steps = sorted(
        (s, s + d) for n, s, d in _line(busiest, MODULES_LINE)
        if n.startswith(step_program) and s >= lo and s + d <= hi
    )
    mine = busy[busiest["name"]]
    gaps = []
    for (_, end), (start, _) in zip(steps, steps[1:]):
        between = _total([
            (max(a, end), min(b, start)) for a, b in mine
            if b > end and a < start
        ])
        gaps.append(max(0, start - end - between) / 1e6)
    coll = _total(union(_clip(
        [ev for ev in _line(busiest, OPS_LINE) if COLLECTIVE.search(ev[0])],
        lo, hi,
    )))

    # breakdown: the ops that took most device time on the busiest chip,
    # and the idle gaps by what the host was doing in them
    per_op: Dict[str, int] = {}
    for n, s, d in _line(busiest, OPS_LINE):
        if s + d > lo and s < hi:
            per_op[_short(n)] = per_op.get(_short(n), 0) + min(s + d, hi) - max(s, lo)
    # a gap inside a running program is the device's own; one between
    # programs goes to the host annotation that covers most of it
    programs = union([
        (s, s + d) for _, s, d in _line(busiest, MODULES_LINE)
    ])
    program_starts = [a for a, _ in programs]
    notes = sorted((s, s + d, n) for n, s, d in host if n != WINDOW)
    note_starts = [s for s, _, _ in notes]
    longest = max((e - s for s, e, _ in notes), default=0)
    per_gap: Dict[str, int] = {}
    edges = [lo] + [t for iv in mine for t in iv] + [hi]
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        i = bisect.bisect_right(program_starts, a) - 1
        if i >= 0 and programs[i][1] >= b:
            best = "inside a device program"
        else:
            best, best_overlap = "unattributed", 0
            first = bisect.bisect_left(note_starts, a - longest)
            for s, e, n in notes[first:bisect.bisect_left(note_starts, b)]:
                overlap = min(e, b) - max(s, a)
                if overlap > best_overlap:
                    best, best_overlap = n, overlap
        per_gap[best] = per_gap.get(best, 0) + (b - a)

    def top(d):
        return [
            [k, v / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]
        ]

    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "chips": len(devices),
        "steps": len(steps),
        "step_device_ms": median([(b - a) / 1e6 for a, b in steps]),
        "host_gap_ms": median(gaps),
        "collective_ms_per_step": coll / 1e6 / len(steps) if steps else None,
        "breakdown": {"device_ops": top(per_op), "idle_gaps": top(per_gap)},
    }


def summary(planes: List[dict], limit: int = 12) -> str:
    """A by-hand look at a trace: planes, lines, counts, first names."""
    out = []
    for p in planes:
        out.append(p["name"])
        for line in p["lines"]:
            names = sorted({_short(e[0]) for e in line["events"]})
            out.append(
                f"  {line['name']}: {len(line['events'])} events, "
                f"{len(names)} names: {names[:limit]}"
            )
    return "\n".join(out)
