"""Reduction of a profiler trace (.xplane.pb) to the numbers the readers use.

Read with jax.profiler.ProfileData alone. A device plane is one named
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per executed
HLO operation and its ``XLA Modules`` line one event per executed
program. Host planes hold the TraceMe events of jax and the benchmark's
own ``bench:<name>`` spans, on the same clock.

- busy: the union of the op intervals on a chip, clipped to the window;
  ``busy_s`` is its mean over the chips used. Idle is the rest.
- time by name: events of a line whose name matches a pattern, summed
  (self time: a ``while`` that contains other events is charged only what
  they leave).
- exposed collective time: the collectives' intervals minus the union of
  every other operation's intervals on that chip.
- idle gaps: the gaps of the busiest chip's union, each charged to the
  deepest host event open at its middle.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[int, int]  # start ns, end ns
Event = Tuple[int, int, str]  # start ns, end ns, name

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MIN_GAP_NS = 20_000


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The parts of union ``a`` that union ``b`` does not cover."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    return subtract([(lo, hi)], busy)


def self_times(events: List[Event]) -> List[Tuple[str, int]]:
    """(name, self ns) per event of one line: its duration less what the
    events nested inside it take."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    selfs = [e[1] - e[0] for e in events]
    stack: List[int] = []
    for i in order:
        s, e, _ = events[i]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][1]:
            selfs[stack[-1]] -= e - s
        stack.append(i)
    return [(events[i][2], max(selfs[i], 0)) for i in range(len(events))]


def time_by_pattern(events: List[Event], pattern: str,
                    lo: Optional[int] = None,
                    hi: Optional[int] = None) -> Tuple[int, int]:
    """(self ns, count) of the events whose name matches, that begin
    inside [lo, hi)."""
    rx = re.compile(pattern)
    ns = count = 0
    for (name, t), (s, _, _) in zip(self_times(events), events):
        if (lo is None or s >= lo) and (hi is None or s < hi) \
                and rx.search(name):
            ns += t
            count += 1
    return ns, count


def exposed_collective_ns(ops: List[Event], lo: int, hi: int) -> int:
    coll = union(clip(((s, e) for s, e, n in ops if COLLECTIVE.search(n)),
                      lo, hi))
    rest = union(clip(((s, e) for s, e, n in ops
                       if not COLLECTIVE.search(n)
                       and not n.startswith(("while", "conditional", "call"))),
                      lo, hi))
    return total(subtract(coll, rest))


def short(name: str) -> str:
    """An operation's name without its HLO text: ``%fusion.3 f32[8,128]``."""
    left, sep, right = name.partition(" = ")
    if not sep:
        return name[:120]
    return (left + " " + right.split("{", 1)[0].split(" ", 1)[0])[:120]


class TraceSummary:
    """The parsed trace of one run's window."""

    def __init__(self, ops: Dict[int, List[Event]],
                 modules: Dict[int, List[Event]], host: List[Event],
                 n_devices: int):
        self.ops = ops  # chip -> events of "XLA Ops"
        self.modules = modules  # chip -> events of "XLA Modules"
        self.host = host  # the events of the benchmark's host thread
        spans = [(s, e) for s, e, n in host if n == "bench:step"]
        every = [(s, e) for evs in ops.values() for s, e, _ in evs]
        if spans:
            self.lo = min(s for s, _ in spans)
            self.hi = max(e for _, e in spans)
        else:
            self.lo = min(s for s, _ in every)
            self.hi = max(e for _, e in every)
        self.chips = sorted(ops)[:n_devices]
        self.busy = {c: union(clip(((s, e) for s, e, _ in ops[c]),
                                   self.lo, self.hi)) for c in self.chips}
        self.window_s = (self.hi - self.lo) / 1e9
        self.busy_s = sum(total(b) for b in self.busy.values()) / 1e9 \
            / max(len(self.chips), 1)

    @property
    def fullest(self) -> int:
        return max(self.chips, key=lambda c: total(self.busy[c]))

    def idle_share(self) -> float:
        """1 - busy over the window, on the busiest chip."""
        return 1.0 - total(self.busy[self.fullest]) / (self.hi - self.lo)

    def op_time(self, pattern: str, line: str = "ops") -> Tuple[float, float]:
        """(seconds, count), mean over the chips, of the events of a
        line ("ops" or "modules") whose name matches."""
        src = self.ops if line == "ops" else self.modules
        ns = count = 0
        for c in self.chips:
            t, k = time_by_pattern(src.get(c, []), pattern, self.lo, self.hi)
            ns += t
            count += k
        return ns / 1e9 / len(self.chips), count / len(self.chips)

    def exposed_collective_s(self) -> float:
        return max(exposed_collective_ns(self.ops[c], self.lo, self.hi)
                   for c in self.chips) / 1e9

    def host_at(self, times: List[int]) -> List[str]:
        """The deepest event of the benchmark's own host thread (the one
        that carries the ``bench:`` spans) open at each of ``times``
        (ascending): one sweep over the thread's events, which nest."""
        events = sorted(self.host, key=lambda ev: (ev[0], -ev[1]))
        out, stack, i = [], [], 0
        for t in times:
            while i < len(events) and events[i][0] <= t:
                while stack and stack[-1][1] <= events[i][0]:
                    stack.pop()
                stack.append(events[i])
                i += 1
            while stack and stack[-1][1] <= t:
                stack.pop()
            out.append(stack[-1][2] if stack else "(no host event)")
        return out

    def breakdown(self, top: int = 10) -> dict:
        chip = self.fullest
        by_name: Dict[str, int] = {}
        for (name, t), (s, _, _) in zip(self_times(self.ops[chip]),
                                        self.ops[chip]):
            if self.lo <= s < self.hi:
                by_name[short(name)] = by_name.get(short(name), 0) + t
        by_host: Dict[str, int] = {}
        long_gaps = [(s, e) for s, e in gaps(self.busy[chip], self.lo, self.hi)
                     if e - s >= MIN_GAP_NS]
        names = self.host_at([(s + e) // 2 for s, e in long_gaps])
        for (s, e), name in zip(long_gaps, names):
            by_host[name] = by_host.get(name, 0) + (e - s)
        rank = lambda d: [[k, v / 1e9] for k, v in sorted(  # noqa: E731
            d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": rank(by_name), "idle_gaps": rank(by_host)}


def _events(line, trim=None) -> List[Event]:
    out = []
    for ev in line.events:
        s = int(ev.start_ns)
        name = ev.name
        if trim is not None:
            name = trim.sub("", name)
        out.append((s, s + int(ev.duration_ns), name))
    return out


_MODULE_ID = re.compile(r"\(\d+\)$")


def parse(path: str, n_devices: int) -> Optional[TraceSummary]:
    """The summary of one .xplane.pb, or None where no device plane has
    an operation in it (a CPU run: it reports no device metric)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[chip] = _events(line)
                elif line.name == "XLA Modules":
                    modules[chip] = _events(line, _MODULE_ID)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = _events(line)
                if any(n.startswith("bench:") for _, _, n in events):
                    host.extend(events)
    ops = {c: evs for c, evs in ops.items() if evs}
    if not ops:
        return None
    return TraceSummary(ops, modules, host, n_devices)


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def summarize(trace_dir: str, n_devices: int) -> Optional[TraceSummary]:
    path = find_xplane(trace_dir)
    return parse(path, n_devices) if path else None


def main(argv=None) -> int:
    """Look at one trace by hand: ``python3 -m benchmark.trace <dir or
    .xplane.pb> [chips]`` prints the planes and lines, the modules and
    the operations that took most self time, and the first events' stats."""
    import sys

    from jax.profiler import ProfileData

    args = list(sys.argv[1:] if argv is None else argv)
    path = args[0]
    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print("plane", plane.name, [ln.name for ln in plane.lines])
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    for ev in list(line.events)[:3]:
                        print(line.name, repr(ev.name),
                              {k: str(v)[:200] for k, v in ev.stats})
            break
    summary = parse(path, int(args[1]) if len(args) > 1 else 1)
    if summary is None:
        print("no device operation in this trace")
        return 0
    print(f"window_s={summary.window_s} busy_s={summary.busy_s} "
          f"idle_share={summary.idle_share()}")
    chip = summary.fullest
    for label, events in (("module", summary.modules.get(chip, [])),
                          ("op", summary.ops[chip])):
        agg: Dict[str, List[int]] = {}
        for name, t in self_times(events):
            a = agg.setdefault(name, [0, 0])
            a[0] += t
            a[1] += 1
        for name, (t, k) in sorted(agg.items(), key=lambda kv: -kv[1][0])[:25]:
            print(f"{label} {t / 1e6:12.3f} ms x{k:<6} {name[:300]}")
    print(summary.breakdown())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
