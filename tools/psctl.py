#!/usr/bin/env python
"""psctl — live introspection CLI for a running parameter-server
cluster.

`kubectl`-shaped operator verbs over the two live surfaces the runtime
already exposes: the telemetry endpoint (``/metrics`` + the ``budget``/
``conns`` JSON paths, telemetry/exporter.py) and the shard servers'
debug verbs (``stats``/``conns``, cluster/shard.py).  Stdlib-only on
purpose — it must start instantly on an operator box and never drag
jax into a shell session.

Usage::

    psctl top    --metrics HOST:PORT [--interval 2] [--iterations 0]
    psctl stats  --shards HOST:PORT[,HOST:PORT...]
    psctl conns  --shards HOST:PORT[,...] | --metrics HOST:PORT
    psctl budget --metrics HOST:PORT [--verb pull] [--json]
    psctl hot    --metrics HOST:PORT [--interval 2] [--iterations 0]
                 [-n 16] [--json]
    psctl slo    --metrics HOST:PORT [--interval 2] [--iterations 0]
                 [--json]
    psctl bytes  --metrics HOST:PORT [--interval 2] [--iterations 0]
                 [--json]
    psctl workloads --metrics HOST:PORT [--interval 2]
                 [--iterations 0] [--json]
    psctl tiers  --metrics HOST:PORT [--interval 2] [--iterations 0]
                 [--json]
    psctl watch  --metrics HOST:PORT [--interval 2] [--iterations 0]
                 [-n 16] [--raw]
    psctl timeline METRIC --metrics HOST:PORT [--json]
    psctl adaptive --metrics HOST:PORT [--json] [-n 10]

``top`` is the `top(1)` of the cluster: it scrapes ``/metrics`` every
``--interval`` seconds, derives rates from counter deltas (updates/sec,
pulls/sec, wire bytes/sec each way) and shows the live gauges
(staleness, queue depths, in-flight pulls) plus the hottest latency-
budget phase.  ``--iterations N`` stops after N frames (0 = forever);
``--raw`` skips the screen-clear escape (pipe/CI friendly).

``hot`` is the live hot-key table (the ``hot`` path on the telemetry
endpoint): the merged sketch top-K — who is actually being hammered —
joined per key with the client-edge lease-cache state (leased where,
entry age, per-key hits) plus each registered cache's hit rate, so an
operator can see at a glance whether the hotcache tier is absorbing a
storm or the celebrities are slipping through
(docs/hotcache.md).  Same ``--interval``/``--iterations``/``--raw``
loop as ``top``; ``--json`` emits the raw payload once.

``slo`` is the operator view for watching a soak (docs/loadgen.md):
one row per declared objective (``fps_slo_burn_rate{slo=,window=}`` ×
``fps_slo_healthy{slo=}`` from the SLOEngine gauges) with its short-
and long-window burn rates and a verdict, then the overload-plane
state underneath — admission rejects per cause
(``fps_serving_rejected_total{reason=}``), shard/serving sheds
(``fps_overload_shed_total{edge=,verb=}``), open circuit breakers
(``fps_overload_breaker_open``) and whether brownout is active
(``fps_brownout_active``).  The verdict column derives from the
published gauges: healthy 1 → ``ok``; healthy 0 with both burns past
1 → ``breach``, else ``burning`` (the engine's page_burn threshold is
not exported, so this is the operator approximation of the
``SLOEngine`` verdict, not its byte-exact reproduction).

``bytes`` is the wire-bytes operator view (docs/compression.md): two
scrapes ``--interval`` apart yield per-verb ``fps_net_bytes_total``
DELTAS (B/s each direction, ``role=server``), the compression plane's
saved-bytes counters (``fps_compression_bytes_saved_total`` — client
push codecs — and ``fps_compression_repl_bytes_saved_total`` — the
replication legs), the derived push compression ratio
(``(push bytes + saved) / push bytes``), and the per-connection
ledger from the telemetry ``conns`` path with its ``proto``/``enc``
columns — a mixed-enc fleet mid-rollout is one table: which
connections negotiated ``q8``, and what the negotiated arm is saving.
The per-connection ``ratio`` column applies the fleet-measured ratio
of that connection's last payload encoding (exact per-conn byte
splits are not tracked — the enc column says which arm the conn is
on, the counters say what the arm saves).

``workloads`` is the per-workload rate table (docs/workloads.md): one
row per registered workload with updates/sec, predictions/sec, sketch
queries/sec and topk/sec derived from the ``workloads`` telemetry
path's cumulative counters between scrapes, plus the serving-verb
latency percentiles (``fps_workload_query_latency_seconds``) and
serving errors.  The first frame shows cumulative totals (in
parentheses) until a second scrape makes rates derivable.

``tiers`` is the two-tier store operator view (docs/tierstore.md): one
row per registered tiered store (primaries ``shard-N``, chain
followers ``shard-N-fK``) from the telemetry endpoint's ``tiers``
path — resident vs configured hot capacity, pinned rows, cold-slab
rows and bytes, the cumulative hit rate, and promote/demote/spill
counters.  With ``--interval`` the hit-rate column becomes a LIVE
rate (hits/misses diffed between scrapes); the first frame shows the
cumulative rate in parentheses.  A process with no tiered shard
answers null and the verb says so (the cluster is not running
``store_backend="tiered"``).

``watch`` is the trend view over ``top``'s numbers: every counter the
endpoint exports (identified from the ``# TYPE`` comment lines) gets a
per-label-set rate derived from deltas between scrapes, and the top-N
rows by current rate render with a unicode sparkline of the rate
history accumulated across frames — a straggling shard or a storming
key family shows up as a diverging trend line, not just a number.
Same ``--interval``/``--iterations``/``--raw`` loop as ``top``.

``timeline`` renders one metric's recorded series window from the
telemetry endpoint's ``timeline`` path (a process-installed
``TimelineRecorder``, telemetry/timeline.py): one row per label-set ×
field (rate/value/p50/p99) with point count, min/max/last, and a
sparkline of the series tail, followed by the recorder's anomaly
ledger entries for that metric.  Accepts the bare registry name or
the ``fps_``-prefixed exporter name; ``--json`` emits the filtered
payload.

``adaptive`` renders the straggler-adaptive runtime's live state from
the telemetry endpoint's ``adaptive`` path (a process-installed
``AdaptiveRuntime``, adaptive/controller.py): a header with the base
bound, ceiling, widen/narrow counts, hedged-push win rate and
rebalance moves, one table row per worker (effective bound × skew
ratio), and the tail of the decision ring — what the control loop did
and why, without a log dive.  ``--json`` emits the raw payload.

``stats`` asks each shard for its one-line JSON stats (rows, pulls,
pushes, restarts, epoch, WAL depth, dedupe-window size) and renders one
table row per shard.  ``conns`` renders each server's live connection
ledger (peer, age, bytes/frames each way).  ``budget`` renders the
per-phase latency budget (telemetry/profiler.py, docs/observability.md);
``--json`` emits the raw artifact (lintable
via ``tools/check_metric_lines.py --budget`` after stamping, or use
the run-report JSON).

Exit codes: 0 ok, 1 unreachable endpoint, 2 usage error.
"""
from __future__ import annotations

import argparse
import json
import re
import socket
import sys
import time
from typing import Dict, List, Optional, Tuple

# -- transport (matches telemetry/exporter.py + utils/net.py idioms) ----------


def scrape(host: str, port: int, path: str = "metrics",
           timeout: float = 5.0) -> str:
    """One-shot line-protocol scrape: send the bare path, read to EOF."""
    with socket.create_connection((host, port), timeout=timeout) as s:
        s.sendall(path.strip().encode("utf-8") + b"\n")
        chunks = []
        while True:
            c = s.recv(1 << 16)
            if not c:
                break
            chunks.append(c)
    return b"".join(chunks).decode("utf-8", "replace")


def request_lines(host: str, port: int, lines: List[str],
                  timeout: float = 5.0) -> List[str]:
    """Line-protocol client: one response line per request line."""
    reqs = [ln.strip() for ln in lines]
    with socket.create_connection((host, port), timeout=timeout) as s:
        s.sendall(("\n".join(reqs) + "\n").encode("utf-8"))
        buf = b""
        out: List[str] = []
        while len(out) < len(reqs):
            chunk = s.recv(1 << 16)
            if not chunk:
                raise ConnectionError(
                    f"peer closed after {len(out)}/{len(reqs)} responses"
                )
            buf += chunk
            *got, buf = buf.split(b"\n")
            out.extend(g.decode("utf-8", "replace") for g in got)
    return out[: len(reqs)]


def parse_addr(addr: str) -> Tuple[str, int]:
    host, _, port = addr.rpartition(":")
    if not host or not port.isdigit():
        raise ValueError(f"{addr!r}: expected HOST:PORT")
    return host, int(port)


# -- Prometheus text parsing --------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>[^}]*)\})?\s+(?P<value>\S+)$"
)
_LABEL_RE = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def parse_prometheus(text: str) -> Dict[Tuple[str, tuple], float]:
    """``{(name, sorted-label-items): value}`` over every sample line."""
    out: Dict[Tuple[str, tuple], float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if m is None:
            continue
        labels = tuple(sorted(
            (k, v.replace(r"\"", '"').replace(r"\\", "\\"))
            for k, v in _LABEL_RE.findall(m.group("labels") or "")
        ))
        try:
            value = float(m.group("value"))
        except ValueError:
            continue  # NaN markers etc. stay out of the rate math
        out[(m.group("name"), labels)] = value
    return out


_TYPE_RE = re.compile(r"^#\s*TYPE\s+(\S+)\s+(\S+)\s*$")


def parse_prometheus_types(text: str) -> Dict[str, str]:
    """``{metric_name: type}`` from the ``# TYPE name kind`` comment
    lines (the lines :func:`parse_prometheus` skips)."""
    out: Dict[str, str] = {}
    for line in text.splitlines():
        m = _TYPE_RE.match(line.strip())
        if m is not None:
            out[m.group(1)] = m.group(2)
    return out


def _sum_named(samples: Dict[Tuple[str, tuple], float], name: str,
               **want: str) -> float:
    total = 0.0
    for (n, labels), v in samples.items():
        if n != name:
            continue
        d = dict(labels)
        if all(d.get(k) == val for k, val in want.items()):
            total += v
    return total


# -- the verbs ----------------------------------------------------------------


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024
    return f"{n:.1f} GiB"


def _render_table(headers: List[str], rows: List[List[str]]) -> str:
    widths = [
        max(len(h), *(len(r[i]) for r in rows)) if rows else len(h)
        for i, h in enumerate(headers)
    ]
    lines = [
        "  ".join(h.ljust(w) for h, w in zip(headers, widths)).rstrip()
    ]
    for r in rows:
        lines.append(
            "  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip()
        )
    return "\n".join(lines)


def cmd_top(args) -> int:
    host, port = parse_addr(args.metrics)
    prev: Optional[Dict[Tuple[str, tuple], float]] = None
    prev_t = 0.0
    shown = 0
    while True:
        try:
            samples = parse_prometheus(scrape(host, port, "metrics"))
            budgets = json.loads(
                scrape(host, port, "budget")
            ).get("budgets", {})
        except OSError as e:
            print(f"psctl: {host}:{port} unreachable: {e}",
                  file=sys.stderr)
            return 1
        now = time.time()
        dt = now - prev_t if prev is not None else None

        def rate(name: str, **want) -> str:
            if prev is None or not dt:
                return "—"
            d = (
                _sum_named(samples, name, **want)
                - _sum_named(prev, name, **want)
            )
            return f"{d / dt:,.0f}"

        lines = [
            f"psctl top — {host}:{port} — "
            f"{time.strftime('%H:%M:%S', time.localtime(now))}",
            "",
            f"updates/sec   {rate('fps_train_events_total')}"
            f"    rounds/sec  {rate('fps_cluster_worker_rounds_total')}",
            f"pulls/sec     {rate('fps_cluster_pulls_total')}"
            f"    pushes/sec  {rate('fps_cluster_pushes_total')}",
            f"wire in/sec   "
            f"{rate('fps_net_bytes_total', direction='in', role='server')}"
            f" B    out/sec     "
            f"{rate('fps_net_bytes_total', direction='out', role='server')}"
            f" B",
            f"staleness     "
            f"{_sum_named(samples, 'fps_cluster_staleness_steps'):g}"
            f"    queue depth "
            f"{_sum_named(samples, 'fps_cluster_shard_queue_depth'):g}"
            f"    inflight pulls "
            f"{_sum_named(samples, 'fps_inflight_pulls'):g}",
        ]
        for verb in sorted(budgets):
            b = budgets[verb]
            if b.get("round_ms") and b.get("top_phase"):
                lines.append(
                    f"budget[{verb}]  round p50 {b['round_ms']} ms — "
                    f"top: {b['top_phase']} ({b['top_pct']}%)"
                )
        screen = "\n".join(lines)
        if not args.raw:
            sys.stdout.write("\x1b[2J\x1b[H")
        print(screen, flush=True)
        prev, prev_t = samples, now
        shown += 1
        if args.iterations and shown >= args.iterations:
            return 0
        time.sleep(args.interval)


def cmd_stats(args) -> int:
    rows: List[List[str]] = []
    for addr in args.shards.split(","):
        host, port = parse_addr(addr.strip())
        try:
            resp = request_lines(host, port, ["stats"])[0]
        except OSError as e:
            print(f"psctl: {addr} unreachable: {e}", file=sys.stderr)
            return 1
        if not resp.startswith("ok "):
            print(f"psctl: {addr}: {resp}", file=sys.stderr)
            return 1
        s = json.loads(resp[3:])
        rows.append([
            str(s.get("shard", "?")), addr.strip(),
            str(s.get("rows", 0)), str(s.get("pulls", 0)),
            str(s.get("pushes", 0)), str(s.get("restarts", 0)),
            str(s.get("epoch", 0)), str(s.get("wal_records", 0)),
            str(s.get("dedupe_pairs", 0)), str(s.get("frozen", 0)),
            "yes" if s.get("alive") else "NO",
        ])
    print(_render_table(
        ["shard", "addr", "rows", "pulls", "pushes", "restarts",
         "epoch", "wal", "dedupe", "frozen", "alive"],
        rows,
    ))
    return 0


def cmd_conns(args) -> int:
    tables: List[Tuple[str, List[dict]]] = []
    if args.shards:
        for addr in args.shards.split(","):
            host, port = parse_addr(addr.strip())
            try:
                resp = request_lines(host, port, ["conns"])[0]
            except OSError as e:
                print(f"psctl: {addr} unreachable: {e}", file=sys.stderr)
                return 1
            if not resp.startswith("ok "):
                print(f"psctl: {addr}: {resp}", file=sys.stderr)
                return 1
            tables.append((addr.strip(), json.loads(resp[3:])))
    elif args.metrics:
        host, port = parse_addr(args.metrics)
        try:
            doc = json.loads(scrape(host, port, "conns"))
        except OSError as e:
            print(f"psctl: {args.metrics} unreachable: {e}",
                  file=sys.stderr)
            return 1
        tables.append((args.metrics, doc.get("conns", [])))
    else:
        print("psctl conns: need --shards or --metrics", file=sys.stderr)
        return 2
    for addr, conns in tables:
        print(f"{addr}: {len(conns)} connection(s)")
        rows = [
            [c.get("peer", "?"), f"{c.get('age_s', 0):.1f}s",
             # negotiated framing, wire substrate (tcp | shm), last
             # payload encoding: the columns that make a mixed
             # line/binary/shared-memory fleet visible mid-rollout
             # (utils/net.py ConnStats; pre-shmem servers omit wire)
             c.get("proto", "line"), c.get("wire", "tcp"),
             c.get("enc", "") or "-",
             _fmt_bytes(c.get("bytes_in", 0)),
             _fmt_bytes(c.get("bytes_out", 0)),
             str(c.get("frames_in", 0)), str(c.get("frames_out", 0)),
             c.get("last_verb", "")]
            for c in conns
        ]
        if rows:
            print(_render_table(
                ["peer", "age", "proto", "wire", "enc", "bytes in",
                 "bytes out", "frames in", "frames out", "last verb"],
                rows,
            ))
    return 0


def cmd_hot(args) -> int:
    host, port = parse_addr(args.metrics)
    shown = 0
    while True:
        try:
            doc = json.loads(scrape(host, port, "hot"))
        except (OSError, ValueError) as e:
            print(f"psctl: {host}:{port} unreachable: {e}",
                  file=sys.stderr)
            return 1
        h = doc.get("hot", {})
        if args.json:
            print(json.dumps(h, indent=2))
            return 0
        lines = [
            f"psctl hot — {host}:{port} — "
            f"{h.get('total_observed', 0)} ids observed "
            f"(count-min error bound ±{h.get('error_bound', 0)})",
        ]
        rows = [
            [
                str(t.get("rank", "?")), str(t.get("key", "?")),
                str(t.get("count", 0)),
                "yes" if t.get("leased") else "—",
                str(t["age"]) if t.get("leased") else "—",
                str(t.get("hits", "—")) if t.get("leased") else "—",
                t.get("cache", "—") if t.get("leased") else "—",
            ]
            for t in h.get("top", [])[: args.n]
        ]
        if rows:
            lines.append("")
            lines.append(_render_table(
                ["rank", "key", "count", "leased", "age", "hits",
                 "cache"],
                rows,
            ))
        else:
            lines.append("(no hot-key traffic observed yet)")
        caches = h.get("caches", {})
        if caches:
            lines.append("")
            for label in sorted(caches):
                c = caches[label]
                rate = c.get("hit_rate")
                lines.append(
                    f"cache[{label}]  hits {c.get('hits', 0)}  "
                    f"misses {c.get('misses', 0)}  "
                    f"hit rate {rate if rate is not None else '—'}  "
                    f"entries {c.get('entries', 0)}  "
                    f"revoked {c.get('revocations', 0)}  "
                    f"stale rejects {c.get('stale_rejects', 0)}"
                )
        screen = "\n".join(lines)
        if not args.raw:
            sys.stdout.write("\x1b[2J\x1b[H")
        print(screen, flush=True)
        shown += 1
        if args.iterations and shown >= args.iterations:
            return 0
        time.sleep(args.interval)


def cmd_workloads(args) -> int:
    """Live per-workload rate table: updates/sec, predictions/sec,
    sketch queries/sec + query latency percentiles, diffed between
    scrapes of the TelemetryServer ``workloads`` path
    (workloads/runtime.workload_table)."""
    host, port = parse_addr(args.metrics)
    prev: Dict[str, dict] = {}
    prev_t: Optional[float] = None
    shown = 0
    rate_keys = (
        ("updates_total", "upd/s"),
        ("predictions_total", "pred/s"),
        ("queries_total", "query/s"),
        ("topk_total", "topk/s"),
    )
    while True:
        try:
            doc = json.loads(scrape(host, port, "workloads"))
        except (OSError, ValueError) as e:
            print(f"psctl: {host}:{port} unreachable: {e}",
                  file=sys.stderr)
            return 1
        table = doc.get("workloads", {})
        if args.json:
            print(json.dumps(table, indent=2, sort_keys=True))
            return 0
        now = time.monotonic()
        dt = (now - prev_t) if prev_t is not None else None
        rows = []
        for name in sorted(table):
            row = table[name]
            cells = [name]
            for key, _label in rate_keys:
                cur = int(row.get(key, 0))
                if dt and name in prev:
                    rate = (cur - int(prev[name].get(key, 0))) / dt
                    cells.append(f"{rate:.1f}")
                else:
                    cells.append(f"({cur})")  # totals until 2nd frame
            cells.append(str(row.get("query_latency_p50_ms", "—")))
            cells.append(str(row.get("query_latency_p99_ms", "—")))
            cells.append(str(row.get("serving_errors_total", 0)))
            rows.append(cells)
        lines = [
            f"psctl workloads — {host}:{port} — rates per second "
            f"(first frame shows cumulative totals in parentheses)",
        ]
        if rows:
            lines.append("")
            lines.append(_render_table(
                ["workload"] + [lab for _, lab in rate_keys]
                + ["q p50 ms", "q p99 ms", "serve errs"],
                rows,
            ))
        else:
            lines.append("(no workload instruments registered yet)")
        screen = "\n".join(lines)
        if not args.raw:
            sys.stdout.write("\x1b[2J\x1b[H")
        print(screen, flush=True)
        prev, prev_t = table, now
        shown += 1
        if args.iterations and shown >= args.iterations:
            return 0
        time.sleep(args.interval)


def cmd_tiers(args) -> int:
    """Live per-store tier table (docs/tierstore.md): resident vs hot
    capacity, pinned rows, slab size, hit rate and the tier-movement
    counters, diffed between scrapes of the TelemetryServer ``tiers``
    path (tierstore/metrics.tiers_snapshot)."""
    host, port = parse_addr(args.metrics)
    prev: Dict[str, dict] = {}
    prev_t: Optional[float] = None
    shown = 0
    while True:
        try:
            doc = json.loads(scrape(host, port, "tiers"))
        except (OSError, ValueError) as e:
            print(f"psctl: {host}:{port} unreachable: {e}",
                  file=sys.stderr)
            return 1
        tiers = doc.get("tiers")
        if args.json:
            print(json.dumps(
                {"tiers": tiers, "run_id": doc.get("run_id")},
                indent=2, sort_keys=True,
            ))
            return 0
        if tiers is None:
            print("psctl: no tiered shard registered on this process "
                  "(the cluster is not running store_backend=\"tiered\")",
                  file=sys.stderr)
            return 1
        now = time.monotonic()
        dt = (now - prev_t) if prev_t is not None else None
        rows = []
        for label in sorted(tiers):
            st = tiers[label]
            hits = int(st.get("hits", 0))
            misses = int(st.get("misses", 0))

            def hit_rate(h: int, m: int) -> str:
                return f"{h / (h + m):.3f}" if (h + m) > 0 else "—"

            if dt and label in prev:
                dh = hits - int(prev[label].get("hits", 0))
                dm = misses - int(prev[label].get("misses", 0))
                rate = hit_rate(dh, dm)
            else:
                rate = f"({hit_rate(hits, misses)})"  # cumulative
            rows.append([
                label, str(st.get("role", "?")),
                f"{st.get('resident_rows', 0)}/"
                f"{st.get('hot_capacity_rows', 0)}",
                str(st.get("pinned_rows", 0)),
                str(st.get("slab_rows", 0)),
                _fmt_bytes(st.get("slab_bytes", 0)),
                rate,
                str(st.get("promotes", 0)),
                str(st.get("demotes", 0)),
                str(st.get("spills", 0)),
            ])
        lines = [
            f"psctl tiers — {host}:{port} — "
            f"{time.strftime('%H:%M:%S', time.localtime())} — "
            f"hit rate is per-interval "
            f"(first frame: cumulative in parentheses)",
        ]
        if rows:
            lines.append("")
            lines.append(_render_table(
                ["store", "role", "resident/cap", "pinned",
                 "slab rows", "slab bytes", "hit rate", "promotes",
                 "demotes", "spills"],
                rows,
            ))
        else:
            lines.append("(tiered stores registered, none reporting)")
        screen = "\n".join(lines)
        if not args.raw:
            sys.stdout.write("\x1b[2J\x1b[H")
        print(screen, flush=True)
        prev, prev_t = tiers, now
        shown += 1
        if args.iterations and shown >= args.iterations:
            return 0
        time.sleep(args.interval)


def _slo_rows(samples: Dict[Tuple[str, tuple], float]) -> List[List[str]]:
    """slo × (burn short, burn long, healthy) → verdict table rows."""
    burns: Dict[str, Dict[str, float]] = {}
    healthy: Dict[str, float] = {}
    for (name, labels), v in samples.items():
        d = dict(labels)
        if name == "fps_slo_burn_rate" and "slo" in d and "window" in d:
            burns.setdefault(d["slo"], {})[d["window"]] = v
        elif name == "fps_slo_healthy" and "slo" in d:
            healthy[d["slo"]] = v
    rows: List[List[str]] = []
    for slo in sorted(set(burns) | set(healthy)):
        short = burns.get(slo, {}).get("short")
        long_ = burns.get(slo, {}).get("long")
        h = healthy.get(slo)
        if h is None:
            verdict = "?"
        elif h >= 1.0:
            verdict = "ok"
        elif (short or 0) > 1.0 and (long_ or 0) > 1.0:
            verdict = "breach"
        else:
            verdict = "burning"
        rows.append([
            slo,
            "—" if short is None else f"{short:.2f}",
            "—" if long_ is None else f"{long_:.2f}",
            verdict,
        ])
    return rows


def cmd_slo(args) -> int:
    host, port = parse_addr(args.metrics)
    shown = 0
    while True:
        try:
            samples = parse_prometheus(scrape(host, port, "metrics"))
        except OSError as e:
            print(f"psctl: {host}:{port} unreachable: {e}",
                  file=sys.stderr)
            return 1
        rows = _slo_rows(samples)
        rejects = {}
        for (name, labels), v in samples.items():
            d = dict(labels)
            if name == "fps_serving_rejected_total" and "reason" in d:
                rejects[d["reason"]] = rejects.get(d["reason"], 0) + v
        sheds = {}
        for (name, labels), v in samples.items():
            d = dict(labels)
            if name == "fps_overload_shed_total":
                key = f"{d.get('edge', '?')}/{d.get('verb', '?')}"
                sheds[key] = sheds.get(key, 0) + v
        breakers_open = _sum_named(samples, "fps_overload_breaker_open")
        brownout = _sum_named(samples, "fps_brownout_active")
        budget_left = _sum_named(samples, "fps_retry_budget_tokens")
        if args.json:
            print(json.dumps({
                "slos": [
                    {"slo": r[0], "burn_short": r[1], "burn_long": r[2],
                     "verdict": r[3]} for r in rows
                ],
                "rejects": rejects,
                "sheds": sheds,
                "breakers_open": breakers_open,
                "brownout_active": bool(brownout),
                "retry_budget_tokens": budget_left,
            }, indent=2))
            return 0
        lines = [
            f"psctl slo — {host}:{port} — "
            f"{time.strftime('%H:%M:%S', time.localtime())}",
            "",
        ]
        if rows:
            lines.append(_render_table(
                ["slo", "burn short", "burn long", "verdict"], rows
            ))
        else:
            lines.append("(no SLO gauges published — is an SLOEngine "
                         "registered?)")
        lines.append("")
        lines.append(
            "rejects  " + (
                "  ".join(
                    f"{k}={int(v)}" for k, v in sorted(rejects.items())
                ) or "—"
            )
        )
        lines.append(
            "sheds    " + (
                "  ".join(
                    f"{k}={int(v)}" for k, v in sorted(sheds.items())
                ) or "—"
            )
        )
        lines.append(
            f"breakers open {breakers_open:g}    brownout "
            f"{'ACTIVE' if brownout else 'off'}    retry budget "
            f"{budget_left:g} tokens"
        )
        screen = "\n".join(lines)
        if not args.raw:
            sys.stdout.write("\x1b[2J\x1b[H")
        print(screen, flush=True)
        shown += 1
        if args.iterations and shown >= args.iterations:
            return 0
        time.sleep(args.interval)


def cmd_bytes(args) -> int:
    host, port = parse_addr(args.metrics)
    prev: Optional[Dict[Tuple[str, tuple], float]] = None
    prev_t = 0.0
    shown = 0
    while True:
        try:
            samples = parse_prometheus(scrape(host, port, "metrics"))
            conns_doc = json.loads(scrape(host, port, "conns"))
        except (OSError, ValueError) as e:
            print(f"psctl: {host}:{port} unreachable: {e}",
                  file=sys.stderr)
            return 1
        now = time.time()
        dt = now - prev_t if prev is not None else None

        # per-verb byte totals + deltas (role=server: the shard edge)
        verbs: Dict[str, Dict[str, float]] = {}
        for (name, labels), v in samples.items():
            if name != "fps_net_bytes_total":
                continue
            d = dict(labels)
            if d.get("role") != "server":
                continue
            row = verbs.setdefault(
                d.get("verb", "?"), {"in": 0.0, "out": 0.0}
            )
            row[d.get("direction", "in")] = (
                row.get(d.get("direction", "in"), 0.0) + v
            )
        saved_push = _sum_named(
            samples, "fps_compression_bytes_saved_total"
        )
        saved_repl = _sum_named(
            samples, "fps_compression_repl_bytes_saved_total"
        )
        push_bytes = verbs.get("push", {}).get("in", 0.0)
        ratio = (
            (push_bytes + saved_push) / push_bytes
            if push_bytes > 0 else None
        )
        conns = conns_doc.get("conns", [])

        def enc_ratio(enc: str) -> str:
            if enc in ("q8", "bf16") and ratio is not None:
                return f"{ratio:.2f}x"
            return "1.00x" if enc in ("f32", "raw") else "—"

        if args.json:
            print(json.dumps({
                "verbs": verbs,
                "compression_bytes_saved": saved_push,
                "compression_repl_bytes_saved": saved_repl,
                "push_ratio": ratio,
                "conns": conns,
            }, indent=2))
            return 0

        def rate(verb: str, direction: str) -> str:
            if prev is None or not dt:
                return "—"
            d = (
                _sum_named(samples, "fps_net_bytes_total",
                           verb=verb, direction=direction,
                           role="server")
                - _sum_named(prev, "fps_net_bytes_total",
                             verb=verb, direction=direction,
                             role="server")
            )
            return f"{d / dt:,.0f}"

        lines = [
            f"psctl bytes — {host}:{port} — "
            f"{time.strftime('%H:%M:%S', time.localtime(now))}",
            "",
        ]
        rows = [
            [verb, _fmt_bytes(row.get("in", 0)),
             _fmt_bytes(row.get("out", 0)),
             rate(verb, "in"), rate(verb, "out")]
            for verb, row in sorted(verbs.items())
        ]
        if rows:
            lines.append(_render_table(
                ["verb", "bytes in", "bytes out", "in B/s", "out B/s"],
                rows,
            ))
        else:
            lines.append("(no fps_net_bytes_total samples — is wire "
                         "accounting on?)")
        lines.append("")
        lines.append(
            f"compression: push saved {_fmt_bytes(saved_push)}"
            + (f"  (ratio {ratio:.2f}x)" if ratio is not None else "")
            + f"    repl saved {_fmt_bytes(saved_repl)}"
        )
        if conns:
            lines.append("")
            lines.append(_render_table(
                ["peer", "proto", "enc", "ratio", "bytes in",
                 "bytes out", "last verb"],
                [
                    [c.get("peer", "?"), c.get("proto", "line"),
                     c.get("enc", "") or "-",
                     enc_ratio(c.get("enc", "")),
                     _fmt_bytes(c.get("bytes_in", 0)),
                     _fmt_bytes(c.get("bytes_out", 0)),
                     c.get("last_verb", "")]
                    for c in conns
                ],
            ))
        screen = "\n".join(lines)
        if not args.raw:
            sys.stdout.write("\x1b[2J\x1b[H")
        print(screen, flush=True)
        prev, prev_t = samples, now
        shown += 1
        if args.iterations and shown >= args.iterations:
            return 0
        time.sleep(args.interval)


def cmd_budget(args) -> int:
    host, port = parse_addr(args.metrics)
    try:
        doc = json.loads(scrape(host, port, "budget"))
    except OSError as e:
        print(f"psctl: {args.metrics} unreachable: {e}", file=sys.stderr)
        return 1
    budgets = doc.get("budgets", {})
    if args.verb:
        budgets = {
            v: b for v, b in budgets.items() if v == args.verb
        }
    if args.json:
        print(json.dumps({"budgets": budgets,
                          "run_id": doc.get("run_id")}, indent=2))
        return 0
    if not budgets:
        print("psctl: no phase observations yet (is the profiler on "
              "and traffic flowing?)")
        return 0
    for verb in sorted(budgets):
        b = budgets[verb]
        print(
            f"{verb}: round p50 {b.get('round_ms')} ms over "
            f"{b.get('rounds')} frames — top cost center: "
            f"{b.get('top_phase')} ({b.get('top_pct')}%), "
            f"coverage {b.get('coverage')}"
        )
        rows = [
            [p["phase"], f"{p['p50_ms']:.4f}", f"{p['mean_ms']:.4f}",
             f"{p['pct']:.1f}%", str(p["count"])]
            for p in b.get("phases", [])
        ]
        print(_render_table(
            ["phase", "p50 ms", "mean ms", "% round", "frames"], rows
        ))
        print()
    return 0


# rate-history sparklines: eight levels, min→max over the window
_SPARK = "▁▂▃▄▅▆▇█"


def _sparkline(values: List[float], width: int = 24) -> str:
    vals = [float(v) for v in values][-width:]
    if not vals:
        return ""
    lo, hi = min(vals), max(vals)
    if hi - lo <= 1e-12:
        return _SPARK[0] * len(vals)
    span = hi - lo
    return "".join(
        _SPARK[int((v - lo) / span * (len(_SPARK) - 1))] for v in vals
    )


def _labels_cell(labels) -> str:
    cell = ",".join(
        f"{k}={v}" for k, v in labels if k != "component"
    )
    return cell or "—"


def cmd_watch(args) -> int:
    host, port = parse_addr(args.metrics)
    prev: Optional[Dict[Tuple[str, tuple], float]] = None
    prev_t = 0.0
    history: Dict[Tuple[str, tuple], List[float]] = {}
    shown = 0
    while True:
        try:
            text = scrape(host, port, "metrics")
        except OSError as e:
            print(f"psctl: {host}:{port} unreachable: {e}",
                  file=sys.stderr)
            return 1
        samples = parse_prometheus(text)
        types = parse_prometheus_types(text)
        now = time.time()
        dt = now - prev_t if prev is not None else 0.0
        if dt > 0:
            for key, v in samples.items():
                if types.get(key[0]) != "counter":
                    continue
                pv = prev.get(key)
                if pv is None:
                    continue
                hist = history.setdefault(key, [])
                hist.append(max(0.0, (v - pv) / dt))
                del hist[:-64]
        ranked = sorted(
            history.items(), key=lambda kv: kv[1][-1], reverse=True
        )
        rows = [
            [name, _labels_cell(labels), f"{hist[-1]:,.1f}",
             _sparkline(hist)]
            for (name, labels), hist in ranked[: args.n]
        ]
        if not args.raw:
            sys.stdout.write("\x1b[2J\x1b[H")
        print(
            f"psctl watch — {host}:{port} — "
            f"{time.strftime('%H:%M:%S', time.localtime(now))} — "
            f"counter rates/sec (top {args.n})"
        )
        if rows:
            print(_render_table(
                ["counter", "labels", "rate/s", "trend"], rows
            ), flush=True)
        else:
            print("(first scrape — rates derivable from the next frame)",
                  flush=True)
        prev, prev_t = samples, now
        shown += 1
        if args.iterations and shown >= args.iterations:
            return 0
        time.sleep(args.interval)


def cmd_timeline(args) -> int:
    host, port = parse_addr(args.metrics)
    try:
        doc = json.loads(scrape(host, port, "timeline"))
    except OSError as e:
        print(f"psctl: {args.metrics} unreachable: {e}", file=sys.stderr)
        return 1
    tl = doc.get("timeline")
    if tl is None:
        print("psctl: no TimelineRecorder installed on this process "
              "(telemetry.timeline.set_timeline)", file=sys.stderr)
        return 1
    want = args.metric
    bare = want[4:] if want.startswith("fps_") else want
    series = [
        s for s in tl.get("series", [])
        if s.get("metric") in (want, bare)
    ]
    anomalies = [
        a for a in tl.get("anomalies", [])
        if a.get("metric") in (want, bare)
    ]
    if args.json:
        print(json.dumps(
            {"metric": bare, "interval_s": tl.get("interval_s"),
             "samples": tl.get("samples"), "series": series,
             "anomalies": anomalies, "run_id": doc.get("run_id")},
            indent=2,
        ))
        return 0
    if not series:
        known = sorted({
            str(s.get("metric")) for s in tl.get("series", [])
        })
        print(f"psctl: no recorded series for {want!r}; recorder "
              f"carries: {', '.join(known) or '(none yet)'}",
              file=sys.stderr)
        return 1
    print(
        f"psctl timeline — {bare} — {len(series)} series, "
        f"{tl.get('samples')} samples @ {tl.get('interval_s')}s"
    )
    rows = []
    for s in series:
        vals = [
            p[1] for p in s.get("points", [])
            if isinstance(p, (list, tuple)) and len(p) == 2
        ]
        if not vals:
            continue
        rows.append([
            _labels_cell(sorted((s.get("labels") or {}).items())),
            str(s.get("field", "?")), str(len(vals)),
            f"{min(vals):.4g}", f"{max(vals):.4g}", f"{vals[-1]:.4g}",
            _sparkline(vals),
        ])
    print(_render_table(
        ["labels", "field", "points", "min", "max", "last", "trend"],
        rows,
    ))
    if anomalies:
        print(f"\n{len(anomalies)} anomaly episode(s):")
        for a in anomalies[-20:]:
            print(
                f"  ts={a.get('ts'):.3f}  {a.get('kind')}  "
                f"labels={a.get('labels')}  score={a.get('score')}"
            )
    return 0


def cmd_adaptive(args) -> int:
    host, port = parse_addr(args.metrics)
    try:
        doc = json.loads(scrape(host, port, "adaptive"))
    except OSError as e:
        print(f"psctl: {args.metrics} unreachable: {e}", file=sys.stderr)
        return 1
    ad = doc.get("adaptive")
    if ad is None:
        print("psctl: no AdaptiveRuntime installed on this process "
              "(adaptive.controller.set_adaptive_runtime)",
              file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(
            {"adaptive": ad, "run_id": doc.get("run_id")}, indent=2,
        ))
        return 0
    hedge = ad.get("hedge") or {}
    issued = hedge.get("issued") or 0
    won = hedge.get("won") or 0
    win_rate = f"{won / issued:.2%}" if issued else "—"
    reb = ad.get("rebalance") or {}
    counts = ad.get("counts") or {}
    print(
        f"psctl adaptive — base_bound={ad.get('base_bound')} "
        f"ceiling={ad.get('bound_ceiling')} ticks={ad.get('ticks')} — "
        f"widen={counts.get('widenings', 0)} "
        f"narrow={counts.get('narrowings', 0)} "
        f"hedged pushes={issued} won={won} ({win_rate}) "
        f"rebalances={reb.get('moves', 0)}"
    )
    rows = [
        [str(w.get("worker")), str(w.get("effective_bound")),
         f"{w.get('skew_ratio', 1.0):.3g}"]
        for w in ad.get("workers", [])
    ]
    if rows:
        print(_render_table(
            ["worker", "effective bound", "skew ratio"], rows
        ))
    else:
        print("(no adaptive clock live — between runs, or the kill "
              "switch is off)")
    decisions = ad.get("decisions") or []
    if decisions:
        print(f"\nlast {min(len(decisions), args.n)} decision(s):")
        for d in decisions[-args.n:]:
            extra = {
                k: v for k, v in d.items()
                if k not in ("ts", "action")
            }
            print(f"  ts={d.get('ts')}  {d.get('action')}  {extra}")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="psctl", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    sub = p.add_subparsers(dest="cmd", required=True)

    top = sub.add_parser("top", help="live top-style view over /metrics")
    top.add_argument("--metrics", required=True, metavar="HOST:PORT")
    top.add_argument("--interval", type=float, default=2.0)
    top.add_argument("--iterations", type=int, default=0,
                     help="stop after N frames (0 = forever)")
    top.add_argument("--raw", action="store_true",
                     help="no screen clear (pipe/CI friendly)")
    top.set_defaults(fn=cmd_top)

    st = sub.add_parser("stats", help="per-shard stats table")
    st.add_argument("--shards", required=True,
                    metavar="HOST:PORT[,HOST:PORT...]")
    st.set_defaults(fn=cmd_stats)

    cn = sub.add_parser("conns", help="live connection ledgers")
    cn.add_argument("--shards", metavar="HOST:PORT[,...]")
    cn.add_argument("--metrics", metavar="HOST:PORT")
    cn.set_defaults(fn=cmd_conns)

    hot = sub.add_parser(
        "hot", help="live hot-key table (sketch top-K × lease state)"
    )
    hot.add_argument("--metrics", required=True, metavar="HOST:PORT")
    hot.add_argument("--interval", type=float, default=2.0)
    hot.add_argument("--iterations", type=int, default=0,
                     help="stop after N frames (0 = forever)")
    hot.add_argument("-n", type=int, default=16,
                     help="rows to show (default 16)")
    hot.add_argument("--raw", action="store_true",
                     help="no screen clear (pipe/CI friendly)")
    hot.add_argument("--json", action="store_true",
                     help="emit the raw payload once")
    hot.set_defaults(fn=cmd_hot)

    slo = sub.add_parser(
        "slo", help="live SLO burn-rate / overload-plane table"
    )
    slo.add_argument("--metrics", required=True, metavar="HOST:PORT")
    slo.add_argument("--interval", type=float, default=2.0)
    slo.add_argument("--iterations", type=int, default=0,
                     help="stop after N frames (0 = forever)")
    slo.add_argument("--raw", action="store_true",
                     help="no screen clear (pipe/CI friendly)")
    slo.add_argument("--json", action="store_true",
                     help="emit the raw payload once")
    slo.set_defaults(fn=cmd_slo)

    by = sub.add_parser(
        "bytes",
        help="per-verb wire-byte rates + compression-ratio table",
    )
    by.add_argument("--metrics", required=True, metavar="HOST:PORT")
    by.add_argument("--interval", type=float, default=2.0)
    by.add_argument("--iterations", type=int, default=0,
                    help="stop after N frames (0 = forever)")
    by.add_argument("--raw", action="store_true",
                    help="no screen clear (pipe/CI friendly)")
    by.add_argument("--json", action="store_true",
                    help="emit the raw payload once")
    by.set_defaults(fn=cmd_bytes)

    wl = sub.add_parser(
        "workloads",
        help="live per-workload rate table (updates/predictions/"
             "queries per second + query latency)",
    )
    wl.add_argument("--metrics", required=True, metavar="HOST:PORT")
    wl.add_argument("--interval", type=float, default=2.0)
    wl.add_argument("--iterations", type=int, default=0,
                    help="stop after N frames (0 = forever)")
    wl.add_argument("--raw", action="store_true",
                    help="no screen clear (pipe/CI friendly)")
    wl.add_argument("--json", action="store_true",
                    help="emit the raw payload once")
    wl.set_defaults(fn=cmd_workloads)

    ti = sub.add_parser(
        "tiers",
        help="two-tier store table: residency, slab size, hit rate, "
             "tier movement",
    )
    ti.add_argument("--metrics", required=True, metavar="HOST:PORT")
    ti.add_argument("--interval", type=float, default=2.0)
    ti.add_argument("--iterations", type=int, default=0,
                    help="stop after N frames (0 = forever)")
    ti.add_argument("--raw", action="store_true",
                    help="no screen clear (pipe/CI friendly)")
    ti.add_argument("--json", action="store_true",
                    help="emit the raw payload once")
    ti.set_defaults(fn=cmd_tiers)

    wa = sub.add_parser(
        "watch",
        help="live counter-rate table with sparkline trends",
    )
    wa.add_argument("--metrics", required=True, metavar="HOST:PORT")
    wa.add_argument("--interval", type=float, default=2.0)
    wa.add_argument("--iterations", type=int, default=0,
                    help="stop after N frames (0 = forever)")
    wa.add_argument("-n", type=int, default=16,
                    help="rows to show (default 16)")
    wa.add_argument("--raw", action="store_true",
                    help="no screen clear (pipe/CI friendly)")
    wa.set_defaults(fn=cmd_watch)

    tlp = sub.add_parser(
        "timeline",
        help="one metric's recorded series window + anomaly ledger",
    )
    tlp.add_argument("metric",
                     help="registry name (bare or fps_-prefixed)")
    tlp.add_argument("--metrics", required=True, metavar="HOST:PORT")
    tlp.add_argument("--json", action="store_true",
                     help="emit the filtered payload")
    tlp.set_defaults(fn=cmd_timeline)

    adp = sub.add_parser(
        "adaptive",
        help="straggler-adaptive runtime: bounds, hedges, rebalances",
    )
    adp.add_argument("--metrics", required=True, metavar="HOST:PORT")
    adp.add_argument("--json", action="store_true",
                     help="emit the raw adaptive payload")
    adp.add_argument("-n", type=int, default=10,
                     help="decision rows to show (default 10)")
    adp.set_defaults(fn=cmd_adaptive)

    bu = sub.add_parser("budget", help="latency-budget phase table")
    bu.add_argument("--metrics", required=True, metavar="HOST:PORT")
    bu.add_argument("--verb", default=None,
                    help="only this verb's budget (default: all)")
    bu.add_argument("--json", action="store_true")
    bu.set_defaults(fn=cmd_budget)

    args = p.parse_args(argv)
    try:
        return args.fn(args)
    except ValueError as e:
        print(f"psctl: {e}", file=sys.stderr)
        return 2
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())
