"""The program's own names in a profiler trace: device time by ``ps.*``
scope, idle gaps by ``fps.*`` span.

``chipbench/trace.py`` keeps the benchmark's ``chipbench.*`` annotations and
the ops' XLA names.  This module reads the same ``.xplane.pb`` again for
what the PROGRAM wrote into it (docs/observability.md, "The span table"):

- host side, ``fps.<component>.<name>``: every ``SpanTracer.span`` of the
  driver and the serving service, on the profiler's clock, one line a thread;
- device side, the ``jax.named_scope`` names in each op's ``op_name`` (the
  stat ``tf_op`` of its event metadata, ``op_scopes``):
  ``ps.pull`` / ``ps.compute`` / ``ps.push`` of
  ``core/transform.make_train_step`` and a logic's own inside ``ps.compute``
  (MF: ``ps.state_pull`` / ``ps.state_push``).  An op belongs to the
  INNERMOST scope of its name; a fusion carries one name, its root's.

``read_xplane`` gives plain data (so the reduction is pinned on a small
recorded trace, ``chipbench/fixtures/``); ``reduce`` gives, for the busiest
chip's step programs inside the window, device ms a step by scope, and every
idle gap between programs put, thread by thread, to the innermost ``fps.*``
span of that thread that covers more than half of it.  A program without
these names (a parent commit) reduces to empty tables, and the readers under
``layer_metrics/`` then report nothing.

    python3 -m chipbench.program_trace <trace dir or .xplane.pb> [step program]

prints what a trace holds (planes, lines, the scoped ops) and its tables.
"""
from __future__ import annotations

import bisect
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

from chipbench.stats import median
from chipbench.trace import (
    DEVICE_PLANE,
    MODULES_LINE,
    OPS_LINE,
    WINDOW,
    _clip,
    _short,
    _total,
    find_xplane,
    union,
)

SPAN_PREFIX = "fps."
SCOPE = re.compile(r"(?:^|/)(ps\.[A-Za-z0-9_]+)(?=/|$)")
NO_SPAN = "unattributed"
Interval = Tuple[int, int]


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one serialized protobuf message: an int
    for a varint, a memoryview for a length-delimited field (a string, or
    a message to walk in turn), ``None`` for a fixed-width one."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind, value = key & 7, None
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, value


def _innermost_scope(op_name: str) -> Optional[str]:
    found = SCOPE.findall(op_name)
    return found[-1] if found else None


def op_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """``{device plane: {op name: innermost ps.* scope}}`` of an
    ``.xplane.pb``.  An op's ``op_name`` reaches the trace as the stat
    ``tf_op`` of its event METADATA (``jit(step)/ps.compute/ps.state_push/
    scatter-add:``), which ``jax.profiler.ProfileData`` does not hand out
    (an event's ``stats`` are its own three: offset, duration, time scale;
    my chip run, PR 26), so the file is walked as protobuf for just that:
    XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 and
    .stat_metadata = 5 (maps: key = 1, value = 2); XEventMetadata.name = 2,
    .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1,
    .str_value = 5, .ref_value = 7 (a stat_metadata id whose name is the
    string).  Where two programs hold an op of one name, the scoped wins:
    only the step program has scopes, and only its ops are summed."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for field, value in _fields(plane):
            if field == 2:
                name = bytes(value).decode()
            elif field in (4, 5):
                entry = dict(_fields(value))
                if field == 4:
                    events.append(entry[2])
                else:
                    stat_names[entry[1]] = bytes(
                        dict(_fields(entry[2])).get(2, b"")
                    ).decode()
        if not DEVICE_PLANE.match(name):
            continue
        scopes = out.setdefault(name, {})
        for metadata in events:
            op, op_name = "", None
            for field, value in _fields(metadata):
                if field == 2:
                    op = bytes(value).decode()
                elif field == 5:
                    stat = dict(_fields(value))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        op_name = (
                            bytes(stat[5]).decode() if 5 in stat
                            else stat_names.get(stat.get(7), "")
                        )
            scope = op_name and _innermost_scope(op_name)
            if scope:
                scopes[op] = scope
    return out


def read_xplane(path: str) -> dict:
    """``{"host": [line], "devices": [plane]}`` as plain data: a host line
    is ``{"name", "events": [[name, start_ns, dur_ns]]}`` with only the
    ``fps.*`` spans and the benchmark's window; a device plane
    ``{"name", "modules": [[name, start, dur]], "ops": [[name, start, dur,
    scope]]}``."""
    from jax.profiler import ProfileData

    host, devices, scopes = [], [], op_scopes(path)
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            scope_of = scopes.get(plane.name, {})
            device = {"name": plane.name, "modules": [], "ops": []}
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    device["modules"] = [
                        [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                        for ev in line.events
                    ]
                elif line.name == OPS_LINE:
                    device["ops"] = [
                        [_short(ev.name), int(ev.start_ns),
                         int(ev.duration_ns), scope_of.get(ev.name)]
                        for ev in line.events
                    ]
            if device["ops"]:
                devices.append(device)
            continue
        for line in plane.lines:
            events = [
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events
                if ev.name.startswith(SPAN_PREFIX) or ev.name == WINDOW
            ]
            if events:
                host.append({"name": line.name, "events": events})
    return {"host": host, "devices": devices}


def _self_times(ops: list) -> List[Tuple[int, int, Optional[str]]]:
    """``(start, self_ns, scope)`` an op: its duration less what ops nested
    in it on the same line cover (a ``while`` holds its body's ops); an op
    with no scope of its own takes the op's that holds it."""
    out, stack = [], []  # stack of [end, index into out]
    for _, start, dur, scope in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            end, parent = stack[-1]
            inside = max(0, min(start + dur, end) - start)
            out[parent][1] -= inside
            if scope is None:
                scope = out[parent][2]
        out.append([start, dur, scope])
        stack.append([start + dur, len(out) - 1])
    return [(s, max(0, d), sc) for s, d, sc in out]


def _innermost(spans: list, starts: List[int], longest: int, a: int, b: int):
    """Of one thread's spans ``(start, end, name)`` sorted by start, the
    innermost that covers more than half of ``[a, b)``, or ``None``."""
    best = None
    first = bisect.bisect_left(starts, a - longest)
    for s, e, n in spans[first:bisect.bisect_left(starts, b)]:
        if 2 * (min(e, b) - max(s, a)) > b - a:
            if best is None or e - s <= best[1] - best[0]:
                best = (s, e, n)
    return best and best[2]


def _within(disjoint: List[Interval], a: int, b: int) -> int:
    """How much of ``[a, b)`` the sorted, disjoint intervals cover."""
    first = max(0, bisect.bisect_right(disjoint, (a, a)) - 1)
    total = 0
    for s, e in disjoint[first:]:
        if s >= b:
            break
        total += max(0, min(e, b) - max(s, a))
    return total


def reduce(trace: dict, step_program: str) -> Optional[dict]:
    """The program-named numbers of one traced window; ``None`` when no
    operation ran on a device."""
    devices = trace["devices"]
    if not devices:
        return None
    window = [
        ev for line in trace["host"] for ev in line["events"] if ev[0] == WINDOW
    ]
    if window:
        lo, hi = window[0][1], window[0][1] + window[0][2]
    else:  # no marker: the span of device activity
        every = [ev for p in devices for ev in p["ops"]]
        lo = min(ev[1] for ev in every)
        hi = max(ev[1] + ev[2] for ev in every)
    busy = {
        p["name"]: union(_clip([ev[:3] for ev in p["ops"]], lo, hi))
        for p in devices
    }
    busiest = max(devices, key=lambda p: _total(busy[p["name"]]))
    mine = busy[busiest["name"]]

    # -- device time a step by scope ---------------------------------------
    steps = sorted(
        (s, s + d) for n, s, d in busiest["modules"]
        if n.startswith(step_program) and s >= lo and s + d <= hi
    )
    step_starts = [s for s, _ in steps]
    by_scope: Dict[Optional[str], int] = {}
    for start, self_ns, scope in _self_times(busiest["ops"]):
        i = bisect.bisect_right(step_starts, start) - 1
        if i >= 0 and start < steps[i][1]:
            by_scope[scope] = by_scope.get(scope, 0) + self_ns
    step_ns = _total(steps)
    scoped_ns = sum(v for k, v in by_scope.items() if k is not None)
    scope_ms = {
        k: v / 1e6 / len(steps) for k, v in by_scope.items() if k is not None
    }

    # -- idle gaps by what each host thread was doing in them ---------------
    programs = union([(s, s + d) for _, s, d in busiest["modules"]])
    program_starts = [a for a, _ in programs]
    threads = []
    for line in trace["host"]:
        spans = sorted(
            (s, s + d, n) for n, s, d in line["events"]
            if n.startswith(SPAN_PREFIX)
        )
        if spans:
            label = "/".join(sorted({n.split(".")[1] for _, _, n in spans}))
            threads.append((
                label, spans, [s for s, _, _ in spans],
                max(e - s for s, e, _ in spans),
            ))
    covered = union([
        (s, e) for _, spans, _, _ in threads for s, e, _ in spans
    ])
    publishes = union([
        (s, e) for _, spans, _, _ in threads for s, e, n in spans
        if n == "fps.train.publish" and lo <= s < hi
    ])
    in_program = between = attributed = under_publish = 0
    per_thread: Dict[str, Dict[str, int]] = {t[0]: {} for t in threads}
    edges = [lo] + [t for iv in mine for t in iv] + [hi]
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        i = bisect.bisect_right(program_starts, a) - 1
        if i >= 0 and programs[i][1] >= b:
            in_program += b - a  # the device's own, no host's
            continue
        between += b - a
        attributed += _within(covered, a, b)
        under_publish += _within(publishes, a, b)
        for label, spans, starts, longest in threads:
            name = _innermost(spans, starts, longest, a, b) or NO_SPAN
            per_thread[label][name] = per_thread[label].get(name, 0) + b - a

    def ms(table: Dict[str, int]) -> Dict[str, float]:
        return {
            k: v / 1e6 for k, v in sorted(table.items(), key=lambda kv: -kv[1])
        }

    return {
        "window_s": (hi - lo) / 1e9,
        "steps": len(steps),
        "step_mean_ms": step_ns / 1e6 / len(steps) if steps else None,
        "step_median_ms": median([(b - a) / 1e6 for a, b in steps]),
        "scope_ms": scope_ms,
        "unscoped_share": (
            1.0 - scoped_ns / step_ns if scope_ms and step_ns else None
        ),
        "idle_in_program_ms": in_program / 1e6,
        "idle_between_programs_ms": between / 1e6,
        "idle_attributed_share": (
            attributed / between if threads and between else None
        ),
        "idle_by_thread_ms": {k: ms(v) for k, v in per_thread.items()},
        "publishes": len(publishes),
        "publish_idle_ms": (
            under_publish / 1e6 / len(publishes) if publishes else None
        ),
    }


def table(reduced: dict) -> str:
    """Both tables as text, for a log."""
    out = [
        f"{reduced['steps']} step programs in {reduced['window_s']:.3f} s: "
        f"mean {reduced['step_mean_ms']} ms, median {reduced['step_median_ms']}"
        f" ms, unscoped share {reduced['unscoped_share']}",
    ]
    out += [f"  {k:<16} {v:10.4f} ms a step" for k, v in reduced["scope_ms"].items()]
    out.append(
        f"idle between programs {reduced['idle_between_programs_ms']:.3f} ms "
        f"(some fps.* span covers {reduced['idle_attributed_share']} of it), "
        f"inside programs {reduced['idle_in_program_ms']:.3f} ms; "
        f"{reduced['publishes']} publishes, idle under one "
        f"{reduced['publish_idle_ms']} ms"
    )
    for thread, gaps in reduced["idle_by_thread_ms"].items():
        out.append(f"  thread {thread}:")
        out += [f"    {k:<32} {v:10.3f} ms" for k, v in gaps.items()]
    return "\n".join(out)


_RUNS: Dict[str, Optional[dict]] = {}  # trace directory -> its reduction


def of_run(ctx: dict) -> Optional[dict]:
    """The reduction of the run a layer-metric reader is called for, parsed
    once a process; ``None`` where the run recorded no device trace."""
    if not ctx["trace"]:
        return None
    from chipbench import run, spec

    where = os.path.join(
        run.OUT_DIR, "trace", f"{ctx['cfg']['name']}.{ctx['traffic']['name']}"
    )
    if where not in _RUNS:
        try:
            path = find_xplane(where)
        except FileNotFoundError:
            _RUNS[where] = None
        else:
            _RUNS[where] = reduce(
                read_xplane(path), spec.family(ctx["cfg"]["family"]).STEP_PROGRAM
            )
        if _RUNS[where]:
            counts: Dict[str, int] = {}
            for s in ctx["spans"]:
                key = f"{s['component']}.{s['name']}"
                counts[key] = counts.get(key, 0) + 1
            print(
                "[chipbench] program trace:\n" + table(_RUNS[where])
                + f"\n[chipbench] spans recorded in the window: "
                f"{sum(counts.values())} {json.dumps(counts, sort_keys=True)}",
                file=sys.stderr, flush=True,
            )
    return _RUNS[where]


def scope_ms(ctx: dict, *scopes: str) -> Optional[float]:
    """Device ms a step under the named scopes; ``None`` if the trace holds
    none of them."""
    by_scope = (of_run(ctx) or {}).get("scope_ms", {})
    found = [by_scope[s] for s in scopes if s in by_scope]
    return sum(found) if found else None


def span_ms(ctx: dict, component: str, name: str) -> List[float]:
    """Durations (ms) of the window's ``SpanTracer`` spans of one name."""
    return [
        s["dur"] * 1e3 for s in ctx["spans"]
        if s["name"] == name and s["component"] == component
    ]


def main(argv: List[str]) -> int:
    from jax.profiler import ProfileData

    path = argv[0] if argv[0].endswith(".pb") else find_xplane(argv[0])
    for plane in ProfileData.from_file(path).planes:
        print(plane.name)
        for line in plane.lines:
            names = sorted({_short(ev.name) for ev in line.events})
            ours = [n for n in names if n.startswith((SPAN_PREFIX, "chipbench."))]
            print(f"  {line.name!r}: {len(names)} names, {ours or names[:8]}")
    for plane, scopes in op_scopes(path).items():
        print(f"{plane}: ops with a ps.* scope")
        for op, scope in sorted(scopes.items(), key=lambda kv: kv[1]):
            print(f"  {scope:<16} {_short(op)}")
    reduced = reduce(read_xplane(path), argv[1] if len(argv) > 1 else "jit_step")
    print(table(reduced) if reduced else "no operation ran on a device")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
