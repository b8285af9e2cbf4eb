"""``mfu_pct`` with the counts taken from a named module beside
flops.py: <module>.<model>(config, work per step, **counters) x steps
over the traced window's seconds, chips and peak. ``counters`` maps a
keyword of the model to [numerator, denominator], two counter families
of the program's registry whose ratio is passed (the CG iterations a
fit); nothing where such a ratio is absent."""

import importlib

from benchmark.readers import counter_ratio


def counted(counters):
    """{keyword: ratio}, or None where a ratio cannot be read."""
    out = {}
    for key, (numerator, denominator) in (counters or {}).items():
        out[key] = counter_ratio.read(None, numerator, denominator)
        if out[key] is None:
            return None
    return out


def read(ctx, module, model, counters=None):
    t = ctx.trace_summary
    steps = ctx.window.get("steps")
    extra = counted(counters)
    if t is None or not steps or ctx.peaks is None or extra is None:
        return None
    counts = importlib.import_module("benchmark." + module)
    work = ctx.window["work"] / steps
    need = getattr(counts, model)(ctx.config, int(work), **extra) * steps
    return 100.0 * need / ctx.window["elapsed_s"] / len(ctx.devices) \
        / ctx.peaks["flops_per_s"]
