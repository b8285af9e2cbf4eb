"""The program's own spans in a profiler trace.

``keystone_tpu.observability.tracing.span(name)`` opens a TraceMe named
``ks:<name>`` on the calling thread, so in a traced run the spans of the
benchmark's thread lie in ``TraceSummary.host`` beside jax's own events
and the ``bench:`` spans, on the clock of the device lines. This module
reduces them to what the span readers report:

- self time by span name: a span's duration less what the ``ks:`` spans
  nested in it cover (jax's own events inside a span stay charged to it);
- idle gaps by span name: each gap of the busiest chip of 20 us or more
  (as ``TraceSummary.breakdown`` takes them) is charged to the deepest
  ``ks:`` span open at its middle, or to no span (the rule of
  ``unattributed_idle_pct``); the tool's tables also cut each gap at the
  spans' edges, which is exact where one gap runs under several spans
  (a read-back, then a host solve);
- the same by ``ks:node:<label>`` span, whole (a node's phases counted to
  the node), so that the nodes that still map per item stand out.

A trace of a program without spans (the parent of the PR that brought
them) has no ``ks:`` event: every reducer returns nothing there.

    python3 -m benchmark.spans <dir kept with --keep-trace> [chips]

prints the tables (a tool for PERF.md; the benchmark's runs never run it).
"""

from __future__ import annotations

import copy
from typing import Dict, List, Optional, Tuple

from benchmark import trace as trace_lib

PREFIX = "ks:"
NODE_PREFIX = "ks:node:"
NO_SPAN = "(no ks: span)"

Event = trace_lib.Event


def ks_events(summary, prefix: str = PREFIX) -> List[Event]:
    """The program's spans that begin inside the window."""
    return [ev for ev in summary.host
            if ev[2].startswith(prefix) and summary.lo <= ev[0] < summary.hi]


def self_ns_by_name(summary) -> Dict[str, Tuple[int, int]]:
    """name -> (self ns, count) over the window's ``ks:`` spans."""
    events = ks_events(summary)
    out: Dict[str, Tuple[int, int]] = {}
    for name, ns in trace_lib.self_times(events):
        t, k = out.get(name, (0, 0))
        out[name] = (t + ns, k + 1)
    return out


def span_self_s(summary, name: str) -> Optional[float]:
    """Self seconds of the spans called ``ks:<name>``, or None where the
    window has none."""
    found = self_ns_by_name(summary).get(PREFIX + name)
    return None if found is None else found[0] / 1e9


def idle_gaps(summary) -> List[Tuple[int, int]]:
    """The busiest chip's idle gaps of MIN_GAP_NS or more."""
    busy = summary.busy[summary.fullest]
    return [(s, e) for s, e in trace_lib.gaps(busy, summary.lo, summary.hi)
            if e - s >= trace_lib.MIN_GAP_NS]


def open_at(summary, events: List[Event], times: List[int]) -> List[str]:
    """``TraceSummary.host_at`` over ``events`` alone: the deepest of them
    open at each of ``times`` (ascending)."""
    view = copy.copy(summary)
    view.host = events
    return view.host_at(times)


def idle_ns_by_span(summary, prefix: str = PREFIX) -> Dict[str, int]:
    """Idle-gap ns by the deepest span (of those whose name begins with
    ``prefix``) open at each gap's middle; NO_SPAN holds the rest."""
    long_gaps = idle_gaps(summary)
    names = open_at(summary, ks_events(summary, prefix),
                    [(s + e) // 2 for s, e in long_gaps])
    out: Dict[str, int] = {}
    for (s, e), name in zip(long_gaps, names):
        if not name.startswith(prefix):
            name = NO_SPAN
        out[name] = out.get(name, 0) + (e - s)
    return out


def deepest_segments(events: List[Event]) -> List[Event]:
    """The nested events' intervals cut so that every instant belongs to
    the deepest event open then: ascending, not overlapping."""
    out: List[Event] = []
    stack: List[Event] = []
    t = 0

    def close(until: int) -> None:
        nonlocal t
        while stack and stack[-1][1] <= until:
            _, end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end

    for ev in sorted(events, key=lambda e: (e[0], -e[1])):
        close(ev[0])
        if stack and ev[0] > t:
            out.append((t, ev[0], stack[-1][2]))
        t = ev[0]
        stack.append(ev)
    close(max((e[1] for e in events), default=0))
    return out


def idle_overlap_ns_by_span(summary, prefix: str = PREFIX) -> Dict[str, int]:
    """Idle-gap ns by the deepest span open, each gap cut at the spans'
    edges; NO_SPAN holds what lies under none."""
    segments = deepest_segments(ks_events(summary, prefix))
    out: Dict[str, int] = {}
    i = 0
    for s, e in idle_gaps(summary):
        covered = 0
        while i < len(segments) and segments[i][1] <= s:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < e:
            a, b, name = segments[j]
            ns = min(b, e) - max(a, s)
            out[name] = out.get(name, 0) + ns
            covered += ns
            j += 1
        out[NO_SPAN] = out.get(NO_SPAN, 0) + (e - s) - covered
    return out


def unattributed_idle_share(summary) -> Optional[float]:
    """The share of idle-gap time under no ``ks:`` span; None where the
    trace has no ``ks:`` span at all or the chip was never idle."""
    if not ks_events(summary):
        return None
    by_span = idle_ns_by_span(summary)
    idle = sum(by_span.values())
    return by_span.get(NO_SPAN, 0) / idle if idle else None


def node_table(summary) -> List[dict]:
    """One row per ``ks:node:<label>``: count, whole seconds, idle-gap
    seconds under it, and the self seconds of the phases inside it."""
    nodes = ks_events(summary, NODE_PREFIX)
    idle = idle_overlap_ns_by_span(summary, NODE_PREFIX)
    inner = sorted(ev for ev in ks_events(summary)
                   if not ev[2].startswith(NODE_PREFIX))
    phases: Dict[str, Dict[str, int]] = {}
    owners = open_at(summary, nodes, [ev[0] for ev in inner])
    for owner, (name, ns) in zip(owners, trace_lib.self_times(inner)):
        by = phases.setdefault(owner, {})
        by[name] = by.get(name, 0) + ns
    rows: Dict[str, dict] = {}
    for s, e, name in nodes:
        row = rows.setdefault(name, {"node": name, "count": 0, "s": 0.0})
        row["count"] += 1
        row["s"] += (e - s) / 1e9
    for name, row in rows.items():
        row["idle_s"] = idle.get(name, 0) / 1e9
        row["phases"] = {k: v / 1e9 for k, v in sorted(
            phases.get(name, {}).items(), key=lambda kv: -kv[1])}
    return sorted(rows.values(), key=lambda r: -r["idle_s"])


def main(argv=None) -> int:
    import os
    import sys

    args = list(sys.argv[1:] if argv is None else argv)
    path = args[0]
    if os.path.isdir(path):
        path = trace_lib.find_xplane(path)
    summary = trace_lib.parse(path, int(args[1]) if len(args) > 1 else 1)
    if summary is None:
        print("no device operation in this trace")
        return 0
    by_middle = idle_ns_by_span(summary)
    by_span = idle_overlap_ns_by_span(summary)
    idle_s = sum(by_span.values()) / 1e9
    print(f"window_s={summary.window_s:.3f} busy_s={summary.busy_s:.3f} "
          f"idle_gaps_s={idle_s:.3f} "
          f"unattributed_idle_share={unattributed_idle_share(summary)}")
    selfs = self_ns_by_name(summary)
    print(f"{'span':44s} {'count':>7s} {'self_s':>9s} {'idle_s':>9s} "
          f"{'by_middle':>9s}")
    for name in sorted(set(selfs) | set(by_span),
                       key=lambda n: -by_span.get(n, 0)):
        ns, count = selfs.get(name, (0, 0))
        print(f"{name:44s} {count:7d} {ns / 1e9:9.3f} "
              f"{by_span.get(name, 0) / 1e9:9.3f} "
              f"{by_middle.get(name, 0) / 1e9:9.3f}")
    print(f"\n{'node':44s} {'count':>7s} {'whole_s':>9s} {'idle_s':>9s}  "
          "phases inside (self s)")
    for row in node_table(summary):
        inside = ", ".join(f"{k[len(PREFIX):]} {v:.3f}"
                           for k, v in list(row["phases"].items())[:4])
        print(f"{row['node']:44s} {row['count']:7d} {row['s']:9.3f} "
              f"{row['idle_s']:9.3f}  {inside}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
