"""``counter_ratio`` times the window's work a step: where the
denominator counts the units of work (rows) that the numerator's events
(chunks) were spent on, wherever in the process they were spent, this
is the events of one step. Nothing where the ratio is absent."""

from benchmark.readers import counter_ratio


def read(ctx, numerator, denominator):
    ratio = counter_ratio.read(ctx, numerator, denominator)
    steps = ctx.window.get("steps")
    if ratio is None or not steps:
        return None
    return ratio * ctx.window["work"] / steps
