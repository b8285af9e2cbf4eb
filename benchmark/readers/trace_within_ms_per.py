"""Device time of the "XLA Ops" events whose name matches ``pattern``,
in ms per ``per`` ("step" or "work"), with two things ``trace_ms_per``
lacks: ``within`` keeps only the events nested in an event that matches
it (a loop's body), and ``inclusive`` counts an event's whole duration
and not its self time (a ``while`` with all that runs in it). Both
patterns are formatted with the configuration's keys and ``rows`` (one
chip's work a step) before they are compiled."""

import re

from benchmark import trace as trace_lib


def seconds_and_count(ctx, pattern, within=None, inclusive=False, **names):
    """(seconds, count), mean over the chips, inside the window."""
    t = ctx.trace_summary
    names = {**{k: v for k, v in ctx.config.items()
                if isinstance(v, (int, float, str))}, **names}
    rx = re.compile(pattern.format(**names))
    outer = re.compile(within.format(**names)) if within else None
    ns = count = 0
    for chip in t.chips:
        events = t.ops.get(chip, [])
        selfs = trace_lib.self_times(events)
        spans = trace_lib.union(
            (s, e) for s, e, n in events if outer.search(n)) if outer else None
        for (s, e, name), (_, self_ns) in zip(events, selfs):
            if not (t.lo <= s < t.hi) or not rx.search(name):
                continue
            if spans is not None and not any(
                    a <= s and e <= b for a, b in spans):
                continue
            ns += (e - s) if inclusive else self_ns
            count += 1
    return ns / 1e9 / len(t.chips), count / len(t.chips)


def read(ctx, pattern, within=None, inclusive=False, per="step"):
    t = ctx.trace_summary
    n = ctx.window.get("steps" if per == "step" else "work")
    if t is None or not n:
        return None
    work = int(ctx.window["work"] / ctx.window["steps"] / len(ctx.devices))
    seconds, count = seconds_and_count(ctx, pattern, within, inclusive,
                                       rows=work)
    return 1e3 * seconds / n if count else None
