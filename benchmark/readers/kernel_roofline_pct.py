"""A kernel's share of its roofline: the least time the chip could take
for the executions a step needs (benchmark/flops.py from shapes: the
larger of operations over peak FLOP/s and bytes over peak bytes/s) over
the device time of the events whose name matches. ``flops_fn`` and
``bytes_fn`` take (configuration, one chip's work per step)."""

from benchmark import flops


def read(ctx, pattern, flops_fn, bytes_fn):
    t = ctx.trace_summary
    steps = ctx.window.get("steps")
    if t is None or not steps or ctx.peaks is None:
        return None
    seconds, count = t.op_time(pattern, line="ops")
    if not count:
        return None
    work = int(ctx.window["work"] / steps / len(ctx.devices))
    least = flops.roofline_s(getattr(flops, flops_fn)(ctx.config, work),
                             getattr(flops, bytes_fn)(ctx.config, work),
                             ctx.peaks)
    return 100.0 * least * steps / seconds
