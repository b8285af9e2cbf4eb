"""Collective time during which nothing else runs on that chip (the
worst chip), in ms per step. Nothing where the trace has no collective."""

from benchmark import trace as trace_lib


def read(ctx):
    t = ctx.trace_summary
    steps = ctx.window.get("steps")
    if t is None or not steps:
        return None
    if not any(trace_lib.COLLECTIVE.search(n)
               for c in t.chips for _, _, n in t.ops[c]):
        return None
    return 1e3 * t.exposed_collective_s() / steps
