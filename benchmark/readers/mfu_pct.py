"""The whole step's share of the chips' peak: flops.<model>(config, work
per step) x steps over the traced window's seconds, chips and peak."""

from benchmark import flops


def read(ctx, model):
    t = ctx.trace_summary
    steps = ctx.window.get("steps")
    if t is None or not steps or ctx.peaks is None:
        return None
    work = ctx.window["work"] / steps
    need = getattr(flops, model)(ctx.config, int(work)) * steps
    return 100.0 * need / ctx.window["elapsed_s"] / len(ctx.devices) \
        / ctx.peaks["flops_per_s"]
