"""``kernel_roofline_pct`` with the counts taken from a named module
beside flops.py, for events that may lie inside a loop. ``pattern`` is
formatted with the configuration's keys and ``rows`` (one chip's work a
step) before it is compiled, so that it can name the data-sized operand;
``within`` (a pattern, formatted the same way) keeps only the events
nested in an event that matches it. ``counters`` as in ``mfu_pct_of``."""

import importlib

from benchmark import flops
from benchmark.readers import trace_within_ms_per
from benchmark.readers.mfu_pct_of import counted


def read(ctx, module, pattern, flops_fn, bytes_fn, within=None,
         counters=None):
    t = ctx.trace_summary
    steps = ctx.window.get("steps")
    extra = counted(counters)
    if t is None or not steps or ctx.peaks is None or extra is None:
        return None
    work = int(ctx.window["work"] / steps / len(ctx.devices))
    seconds, count = trace_within_ms_per.seconds_and_count(
        ctx, pattern, within, rows=work)
    if not count:
        return None
    counts = importlib.import_module("benchmark." + module)
    least = flops.roofline_s(
        getattr(counts, flops_fn)(ctx.config, work, **extra),
        getattr(counts, bytes_fn)(ctx.config, work, **extra), ctx.peaks)
    return 100.0 * least * steps / seconds
