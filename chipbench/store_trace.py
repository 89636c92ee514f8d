"""Device time a step by STORE in a step over several
(``core/store.StoreGroup``): the program labels every op of one store's pull
or push ``store.<name>`` beside the ``ps.*`` phase it stands in
(``core/store.pull_counted`` / ``push_counted``'s ``store``), so an op's name
reads ``jit(step)/ps.push/store.deep/ps.combine/...``.

``chipbench/program_trace.py`` puts an op to the INNERMOST ``ps.*`` scope of
its name, whichever store it served (``store.pull_device_ms`` sums both
stores' pulls).  This module reduces the same trace the same way, an op put
instead to ``<phase>.<store>``, ``phase`` ``pull`` or ``push`` by the
OUTERMOST of ``ps.pull`` / ``ps.push`` in its name (a rule's combine, rule and
write-back all stand in ``ps.push``): ``program_trace``'s own reduction, read
with another rule for an op's label.  A program without the labels (every
step over one store, the parent) reduces to nothing, and the readers report
nothing.
"""
from __future__ import annotations

import os
import re
from typing import Dict, Optional

from chipbench import program_trace
from chipbench.trace import find_xplane

LABEL = re.compile(
    r"(?:^|/)ps\.(pull|push)(?:/[^/]+)*?/store\.([A-Za-z0-9_]+)(?=/|$)")
_RUNS: Dict[str, Dict[str, float]] = {}  # trace directory -> ms by label


def label_of(op_name: str) -> Optional[str]:
    """``pull.<store>`` / ``push.<store>`` of an op's name, or ``None``."""
    found = LABEL.search(op_name)
    return found and f"{found.group(1)}.{found.group(2)}"


def reduce(path: str, step_program: str) -> Dict[str, float]:
    """``{label: device ms a step}`` of an ``.xplane.pb``."""
    by_scope = program_trace._innermost_scope
    program_trace._innermost_scope = label_of
    try:
        reduced = program_trace.reduce(
            program_trace.read_xplane(path), step_program)
    finally:
        program_trace._innermost_scope = by_scope
    return (reduced or {}).get("scope_ms", {})


def store_ms(ctx: dict, *labels: str) -> Optional[float]:
    """Device ms a step under the labels, of the run a reader is called
    for (parsed once a process); ``None`` if the trace holds none."""
    if not ctx["trace"]:
        return None
    from chipbench import run, spec

    where = os.path.join(
        run.OUT_DIR, "trace", f"{ctx['cfg']['name']}.{ctx['traffic']['name']}")
    if where not in _RUNS:
        try:
            _RUNS[where] = reduce(
                find_xplane(where),
                spec.family(ctx["cfg"]["family"]).STEP_PROGRAM)
        except FileNotFoundError:
            _RUNS[where] = {}
    found = [_RUNS[where][k] for k in labels if k in _RUNS[where]]
    return sum(found) if found else None
